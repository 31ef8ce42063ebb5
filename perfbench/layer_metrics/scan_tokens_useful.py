"""scan_tokens_useful.<serving mix>: of the chunk positions the PREFILL
program's state-space scan ran over, the share that were tokens, in percent,
over the nominal window: the difference of the engine's counters
``ssm_tokens_live.prefill`` and ``ssm_tokens_computed.prefill`` between the
two ``/healthz`` bodies. A prompt's final chunk is padded to the chunk's
length and a prefill group to its next size; the scan computes over all of
it and advances the state over the tokens only. A program without these
counters (no layer that counts them): nothing to read."""

from perfbench.counters import window_difference


def read(res):
    live = window_difference(res, "ssm_tokens_live.prefill")
    computed = window_difference(res, "ssm_tokens_computed.prefill")
    if not live or not computed:
        return None
    return 100.0 * live / computed
