"""Collective and memory budgets: what a program is ALLOWED to emit/hold.

Generalises the hard-coded per-strategy assertions of
tests/test_hlo_collectives.py into a reusable contract object:

- ``required``: base opcodes that MUST appear (the collectives the
  strategy's design promises — FSDP gathers+scatters, DDP all-reduces,
  ring permutes, EP all-to-alls);
- ``forbidden``: opcodes that must NOT appear (a sharding edit that sneaks
  an all-gather into a DDP step is exactly the silent regression this
  subsystem exists to catch);
- ``max_counts``: optional per-opcode instruction-count ceilings for
  programs whose collective count is part of the perf contract (e.g. ONE
  gradient all-reduce at the accumulation boundary).

``expected_budget`` derives the contract for a MeshConfig the same way the
strategies themselves are written (parallel/explicit.py, parallel/pipeline.py).

``MemoryBudget`` is the peer contract for bytes (analysis/memory.py's
static peak-HBM estimate): pinned ``max_live_bytes`` ceilings per
registered program, a hard cap on the bytes a donated input may fail to
alias (``check_memory`` names the parameter when XLA rejects a donation),
and an optional ceiling on the donated buffer itself (the int8-pool
contract — an upcast to f32 triples the pool and must fail the audit).
"""

from __future__ import annotations

import dataclasses

from pytorch_distributed_tpu.analysis.hlo import (
    HLO_COLLECTIVES,
    AsyncCollective,
)
from pytorch_distributed_tpu.analysis.report import Finding
from pytorch_distributed_tpu.config import MeshConfig, ModelConfig


@dataclasses.dataclass(frozen=True)
class CollectiveBudget:
    required: frozenset = frozenset()
    forbidden: frozenset = frozenset()
    max_counts: dict = dataclasses.field(default_factory=dict)
    note: str = ""
    # Overlap contract: when not None, every async collective
    # start/done pair the compiled module schedules must have at least
    # this many compute instructions between start and done
    # (analysis/hlo.async_collective_pairs) — the machine-checkable form
    # of "the transfer is hidden under compute, not just async-shaped".
    # Backends that emit synchronous collectives (XLA:CPU) produce no
    # pairs; the check then reports an info note instead of passing
    # silently (check_async_overlap).
    async_min_compute: int | None = None

    def __post_init__(self) -> None:
        object.__setattr__(self, "required", frozenset(self.required))
        object.__setattr__(self, "forbidden", frozenset(self.forbidden))
        for op in self.required | self.forbidden | set(self.max_counts):
            if op not in HLO_COLLECTIVES:
                raise ValueError(
                    f"unknown collective opcode {op!r}; known: "
                    f"{HLO_COLLECTIVES}"
                )
        overlap = self.required & self.forbidden
        if overlap:
            raise ValueError(
                f"opcodes both required and forbidden: {sorted(overlap)}"
            )


NO_COLLECTIVES = CollectiveBudget(
    forbidden=frozenset(HLO_COLLECTIVES),
    note="single-device program: any collective is a bug",
)


# Instruction-count ceilings for the registered cases whose collective
# count IS the perf contract, measured once on the tiny registry models
# (XLA:CPU, jax 0.4.37) and pinned. The numbers are per-HLO-module
# instruction counts, not logical collectives: XLA emits one all-reduce
# per psum operand, so DDP's "ONE gradient all-reduce" (a single variadic
# psum over the 15-leaf grad tree) plus the loss/metric reductions lands
# at 17 instructions; ZeRO-3's just-in-time gathers are per-leaf, per
# direction (forward gather + remat re-gather in backward), and its
# reduce-scatters are the gathers' AD transposes. A future edit that
# re-gathers params twice, loses the accumulate-locally/reduce-once
# structure, or sneaks a second grad reduction blows the ceiling.
#
# The latency-hiding schedule cases (PR 3):
# - fsdp_prefetch (prefetch_buffers=1 on the 2-layer registry model =
#   one 2-layer window): the window body textually contains W=2 copies
#   of each per-leaf gather/scatter, so the STATIC instruction count
#   roughly doubles while the DYNAMIC per-step collective count is
#   unchanged (W x per-body collectives x L/W trip count). The ceiling
#   pins that static shape — growth past it means the window gained a
#   third gather of the same leaf or lost the re-gather structure.
# - zero2_bucketed (rs_buckets=2): the per-leaf boundary psum_scatters
#   coalesce into exactly rs_buckets bucket collectives — THE schedule
#   contract; a 3rd reduce-scatter means bucketing silently broke.
# - zero3_decode_prefetch (the serving engine's ZeRO-3 decode_run,
#   prefetch_buffers=1 on the 2-layer registry model = one 2-layer
#   window): the partitioner's per-leaf layer gathers appear W=2 times
#   in the window body plus the up-front non-block gathers; growth past
#   the ceiling means a layer's shards started gathering twice per use
#   (or the embedding/head gathers moved inside the token loop). The
#   all-reduces are the partitioner's logit/softmax reductions.
STABLE_MAX_COUNTS: dict[str, dict[str, int]] = {
    "ddp": {"all-reduce": 17},
    "fsdp": {"all-gather": 27, "reduce-scatter": 16, "all-reduce": 2},
    "fsdp_prefetch": {
        "all-gather": 51, "reduce-scatter": 28, "all-reduce": 2,
    },
    "zero2_bucketed": {"reduce-scatter": 2, "all-reduce": 18},
    "zero3_decode_prefetch": {"all-gather": 28, "all-reduce": 11},
    # Slot-batched TP decode step (serving/engine.BatchedDecodeEngine):
    # exactly the scanned block body's two Megatron psums (attention
    # c_proj + MLP c_proj), emitted ONCE each thanks to the layer scan —
    # and, because every per-row quantity (pos, fold, sampling params,
    # active pattern) is a traced operand, this count is INVARIANT to how
    # many rows are active: admissions/retirements never touch the
    # program. Growth means per-row handling leaked a collective (e.g.
    # sampling started psumming per row) or the scan unrolled.
    "decode_batched_step_tp": {"all-reduce": 2},
}


def pin_max_counts(budget: CollectiveBudget, case: str) -> CollectiveBudget:
    """``budget`` with the STABLE_MAX_COUNTS ceilings for ``case``."""
    counts = STABLE_MAX_COUNTS[case]
    return dataclasses.replace(
        budget,
        max_counts={**budget.max_counts, **counts},
        note=f"{budget.note}; max_counts pinned ({case})".strip("; "),
    )


@dataclasses.dataclass(frozen=True)
class MemoryBudget:
    """Byte ceilings for one compiled program's static memory estimate.

    ``max_live_bytes``: ceiling on the liveness-scan peak
    (memory.MemoryEstimate.peak_live_bytes). Pinned per registered case in
    ``STABLE_MEMORY_BUDGETS`` the way STABLE_MAX_COUNTS pins collective
    counts: measured once on the tiny registry models and frozen, so a
    regression that doubles a live buffer blows the ceiling.
    ``max_unaliased_donated_bytes``: how many bytes of DONATED input XLA
    may fail to alias before the audit errors. 0 for the serving engines
    (in-place cache reuse IS the contract); a measured allowance for
    training cases that tolerate the odd reshaped optimizer slot.
    ``max_donated_bytes``: optional ceiling on the donated argument's own
    size — the quantized-pool contract (an int8 page pool silently upcast
    to f32 is ~4x these bytes and must fail loudly, independent of what
    the rest of the program does).
    ``max_loop_body_peak_bytes``: optional ceiling on the largest while-body
    liveness peak — the steady-state-HBM contract for decode loops, where
    the token loop's per-iteration footprint (not the one-shot entry
    setup) is what an accelerator actually holds for the life of a
    generation. Pinned for the serving decode cases.
    """

    max_live_bytes: int | None = None
    max_unaliased_donated_bytes: int = 0
    max_donated_bytes: int | None = None
    max_loop_body_peak_bytes: int | None = None
    note: str = ""


def check_memory(
    estimate,
    budget: MemoryBudget | None,
    *,
    donated_params: frozenset = frozenset(),
) -> tuple[list[Finding], dict]:
    """Diff a program's static memory estimate against its byte budget.

    ``estimate``: analysis/memory.estimate_memory over the compiled
    module text. ``donated_params``: the entry-parameter numbers the call
    site donated (audit.donated_param_numbers) — every one of them should
    appear in the accepted input_output_alias map; one that does not is
    double-buffered at runtime, and the finding NAMES it (parameter
    number, HLO name, shape, bytes) so the shape/dtype change that broke
    the alias is findable. Returns (findings, stats); a None budget
    records stats without judging them.
    """
    unaliased = sorted(donated_params - estimate.aliased_params)
    unaliased_bytes = estimate.param_bytes(unaliased)
    donated_bytes = estimate.param_bytes(donated_params)
    loop_peaks = {
        name: est.peak_live_bytes
        for name, est in estimate.loop_bodies().items()
    }
    stats = {
        "peak_live_bytes": estimate.peak_live_bytes,
        "raw_peak_bytes": estimate.raw_peak_bytes,
        "alias_saved_bytes": estimate.alias_saved_bytes,
        "parameter_bytes": estimate.parameter_bytes,
        "donated_bytes": donated_bytes,
        "unaliased_donated_bytes": unaliased_bytes,
        "unaliased_donated_params": unaliased[:16],
        "loop_body_peak_bytes": (
            max(loop_peaks.values()) if loop_peaks else 0
        ),
    }
    findings: list[Finding] = []
    if budget is None:
        return findings, stats
    stats["budget"] = {
        "max_live_bytes": budget.max_live_bytes,
        "max_unaliased_donated_bytes": budget.max_unaliased_donated_bytes,
        "max_donated_bytes": budget.max_donated_bytes,
        "max_loop_body_peak_bytes": budget.max_loop_body_peak_bytes,
        "note": budget.note,
    }

    if (
        budget.max_live_bytes is not None
        and estimate.peak_live_bytes > budget.max_live_bytes
    ):
        findings.append(
            Finding(
                checker="memory",
                code="memory-budget-exceeded",
                severity="error",
                message=(
                    f"static peak {estimate.peak_live_bytes:,} bytes > "
                    f"pinned ceiling {budget.max_live_bytes:,} — a live "
                    "buffer grew (lost alias, upcast, or a new "
                    "materialisation); re-pin only if the growth is a "
                    "deliberate contract change"
                ),
                detail={
                    "peak_live_bytes": estimate.peak_live_bytes,
                    "max_live_bytes": budget.max_live_bytes,
                },
            )
        )
    if unaliased_bytes > budget.max_unaliased_donated_bytes:
        for pn in unaliased:
            p = estimate.parameters.get(pn)
            findings.append(
                Finding(
                    checker="memory",
                    code="donated-param-not-aliased",
                    severity="error",
                    message=(
                        f"donated parameter {pn}"
                        + (
                            f" (%{p.name}: {p.shape}, {p.bytes:,} bytes)"
                            if p is not None else ""
                        )
                        + " has NO accepted output alias — XLA rejected "
                        "the donation, so those bytes are double-buffered "
                        "every call; find the shape/dtype change between "
                        "this input and the output meant to reuse it"
                    ),
                    detail={
                        "param_number": pn,
                        "param_name": p.name if p else None,
                        "shape": p.shape if p else None,
                        "bytes": p.bytes if p else None,
                        "allowance": budget.max_unaliased_donated_bytes,
                    },
                )
            )
    elif unaliased:
        findings.append(
            Finding(
                checker="memory",
                code="unaliased-donated-within-allowance",
                severity="info",
                message=(
                    f"{len(unaliased)} donated parameter(s) "
                    f"({unaliased_bytes:,} bytes) not aliased, within the "
                    f"budget's {budget.max_unaliased_donated_bytes:,}-byte "
                    "allowance"
                ),
                detail={"params": unaliased[:16],
                        "bytes": unaliased_bytes},
            )
        )
    if (
        budget.max_loop_body_peak_bytes is not None
        and stats["loop_body_peak_bytes"] > budget.max_loop_body_peak_bytes
    ):
        findings.append(
            Finding(
                checker="memory",
                code="loop-body-peak-exceeded",
                severity="error",
                message=(
                    f"largest while-body liveness peak "
                    f"{stats['loop_body_peak_bytes']:,} bytes > pinned "
                    f"ceiling {budget.max_loop_body_peak_bytes:,} — the "
                    "steady-state decode-loop footprint grew (a "
                    "per-iteration buffer stopped aliasing or a setup "
                    "tensor moved inside the token loop)"
                ),
                detail={
                    "loop_body_peak_bytes": stats["loop_body_peak_bytes"],
                    "max_loop_body_peak_bytes":
                        budget.max_loop_body_peak_bytes,
                    "loop_bodies": loop_peaks,
                },
            )
        )
    if (
        budget.max_donated_bytes is not None
        and donated_bytes > budget.max_donated_bytes
    ):
        findings.append(
            Finding(
                checker="memory",
                code="donated-bytes-exceeded",
                severity="error",
                message=(
                    f"donated argument is {donated_bytes:,} bytes > "
                    f"pinned ceiling {budget.max_donated_bytes:,} — the "
                    "donated buffer itself grew (e.g. an int8 pool "
                    "silently upcast to full precision)"
                ),
                detail={
                    "donated_bytes": donated_bytes,
                    "max_donated_bytes": budget.max_donated_bytes,
                },
            )
        )
    return findings, stats


# Pinned static-memory ceilings per registered audit case, the bytes
# counterpart of STABLE_MAX_COUNTS: max_live_bytes is the measured
# liveness-scan peak of the compiled program on the tiny registry
# models (8 virtual CPU devices), frozen exactly — any growth is a
# regression until adjudicated and re-pinned (shrinkage passes: these
# are ceilings). max_donated_bytes pins the donated cache/pool argument
# itself for the serving cases, where its size IS the claim: the dense
# slot cache and the paged pool are both 65_536 B at the registry's
# equal-slots config (pool_pages*page_size == slots*max_len — paged
# wins by allocating FEWER pages, not smaller ones), and the int8 pool
# is 20_480 B = 0.3125x f32, exactly (head_dim+4)/(4*head_dim) at
# head_dim 16 (per-token f32 scales amortized over the head); an
# upcast to f32 lands at 65_536+ and fails donated-bytes-exceeded.
# max_unaliased_donated_bytes stays at its 0 default everywhere —
# measured: XLA accepts EVERY donated alias in every program at HEAD.
# The 17 decode_* programs that run models/decode.forward (not the
# kv_export/kv_import ones) were re-measured under jax 0.9.0 when the KV
# cache moved from the layer scan's xs/ys into its carry: the same
# programs with the cache in xs/ys read higher under the same jax in
# max_live_bytes, max_loop_body_peak_bytes or the cost table's
# max_hbm_bytes (decode_paged_step: 830_821 / 565_392 / 974_819), so a
# return to xs/ys fails a pin in every one of them.
# Re-pin procedure: docs/ANALYSIS.md §6.
STABLE_MEMORY_BUDGETS: dict[str, MemoryBudget] = {
    "baseline": MemoryBudget(max_live_bytes=4_784_172),
    "train_guard": MemoryBudget(max_live_bytes=4_783_176),
    "ddp": MemoryBudget(max_live_bytes=2_458_408),
    "ddp_bf16": MemoryBudget(
        max_live_bytes=2_758_952,
        note="above f32 ddp: the f32 grad accumulator + bf16 activation "
             "copies coexist at the backward peak on this tiny model",
    ),
    "fsdp": MemoryBudget(max_live_bytes=709_868),
    "zero2": MemoryBudget(max_live_bytes=2_090_536),
    "fsdp_prefetch": MemoryBudget(
        max_live_bytes=733_152,
        note="the +1-layer prefetch window costs ~23 KiB over plain "
             "fsdp — the bounded-extra-live-bytes overlap claim",
    ),
    "zero2_bucketed": MemoryBudget(max_live_bytes=2_090_280),
    "tp": MemoryBudget(max_live_bytes=1_977_900),
    "ring": MemoryBudget(max_live_bytes=3_139_616),
    "ulysses": MemoryBudget(max_live_bytes=2_755_628),
    "ep": MemoryBudget(max_live_bytes=5_391_952),
    "pipeline": MemoryBudget(max_live_bytes=3_966_421),
    "pipeline_1f1b": MemoryBudget(
        max_live_bytes=1_540_180,
        note="~0.39x GPipe peak: 1F1B's bounded in-flight microbatches, "
             "reproduced from static bytes alone",
    ),
    "decode_prefill": MemoryBudget(
        max_live_bytes=658_145, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=393_368,
    ),
    "decode_step": MemoryBudget(
        max_live_bytes=476_529, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=211_760,
    ),
    "zero3_decode_prefetch": MemoryBudget(
        max_live_bytes=456_825, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=293_090,
    ),
    "decode_batched_prefill": MemoryBudget(
        max_live_bytes=723_687, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=393_368,
    ),
    "decode_batched_step": MemoryBudget(
        max_live_bytes=749_885, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=484_472,
    ),
    "decode_batched_step_tp": MemoryBudget(
        max_live_bytes=217_277, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=147_944,
    ),
    "decode_paged_prefill": MemoryBudget(
        max_live_bytes=727_850, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=463_016,
    ),
    "decode_paged_step": MemoryBudget(
        max_live_bytes=749_933, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=484_504,
    ),
    "decode_paged_prefill_q8": MemoryBudget(
        max_live_bytes=372_394, max_donated_bytes=20_480,
        max_loop_body_peak_bytes=294_152,
        note="int8 pool + per-token scales: 0.3125x the f32 pool at "
             "head_dim 16; an f32 upcast fails donated-bytes-exceeded",
    ),
    "decode_paged_step_q8": MemoryBudget(
        max_live_bytes=369_517, max_donated_bytes=20_480,
        max_loop_body_peak_bytes=290_568,
        note="int8 pool + per-token scales: 0.3125x the f32 pool at "
             "head_dim 16; an f32 upcast fails donated-bytes-exceeded",
    ),
    "decode_batched_step_tp_q8": MemoryBudget(
        max_live_bytes=145_469, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=98_664,
    ),
    "decode_batched_spec_step": MemoryBudget(
        max_live_bytes=759_429, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=493_976,
    ),
    "decode_paged_spec_step": MemoryBudget(
        max_live_bytes=759_525, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=493_912,
    ),
    "decode_batched_step_tp_spec": MemoryBudget(
        max_live_bytes=222_213, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=152_792,
    ),
    "decode_paged_prefill_lora": MemoryBudget(
        max_live_bytes=768_815, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=497_943,
    ),
    "decode_paged_step_lora": MemoryBudget(
        max_live_bytes=785_809, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=514_356,
    ),
    "decode_batched_step_tp_lora": MemoryBudget(
        max_live_bytes=237_793, max_donated_bytes=16_384,
        max_loop_body_peak_bytes=199_244,
    ),
    "decode_paged_kv_export": MemoryBudget(
        max_live_bytes=73_736,
        max_loop_body_peak_bytes=0,
        note="pool + gathered pages both live: export does NOT donate "
             "(the source row must survive until complete_handoff); "
             "no while loop, so the body peak is zero by construction",
    ),
    "decode_paged_kv_import": MemoryBudget(
        max_live_bytes=114_716, max_donated_bytes=65_536,
        max_loop_body_peak_bytes=73_752,
    ),
    "decode_paged_kv_import_q8": MemoryBudget(
        max_live_bytes=33_824, max_donated_bytes=20_480,
        max_loop_body_peak_bytes=18_456,
        note="int8 pages + per-token scale leaves scatter as-is: "
             "0.3125x the f32 import's pool bytes",
    ),
    "decode_paged_kv_import_tp": MemoryBudget(
        max_live_bytes=57_372, max_donated_bytes=32_768,
        max_loop_body_peak_bytes=36_888,
        note="per-shard bytes: each tensor=2 shard scatters its own "
             "head slice, half the single-device pool",
    ),
    "ddp_pjit": MemoryBudget(max_live_bytes=2_458_808),
    "fsdp_pjit": MemoryBudget(max_live_bytes=1_094_776),
    "zero2_pjit": MemoryBudget(max_live_bytes=1_558_768),
    "tp_pjit": MemoryBudget(max_live_bytes=1_977_900),
    "ring_pjit": MemoryBudget(max_live_bytes=2_737_788),
    "ep_pjit": MemoryBudget(max_live_bytes=6_461_028),
}


def memory_budget_for(case: str) -> MemoryBudget:
    """The pinned STABLE_MEMORY_BUDGETS entry for ``case``.

    KeyError (with the fix spelled out) when the case has no pin: every
    registered program must carry a memory budget, so a new engine
    program cannot ship audit-unpinned.
    """
    try:
        return STABLE_MEMORY_BUDGETS[case]
    except KeyError:
        raise KeyError(
            f"no pinned memory budget for registered case {case!r} — "
            "measure it (scripts/audit.py --case "
            f"{case} --only memory --json r.json, read "
            "summary.memory) and add a STABLE_MEMORY_BUDGETS entry "
            "(docs/ANALYSIS.md §6 documents the re-pin procedure)"
        ) from None


@dataclasses.dataclass(frozen=True)
class CostBudget:
    """Pinned per-step throughput-resource ceilings for one program.

    The three quantities analysis/cost.py derives statically from the
    scheduled HLO — FLOPs executed, HBM bytes moved, collective wire
    bytes — frozen per registered case in ``STABLE_COST_BUDGETS`` the
    way STABLE_MEMORY_BUDGETS freezes peak-live bytes. Exceeding any
    ceiling is a perf regression (a doubled matmul, an upcast page
    pool, an un-coalesced collective) until adjudicated and re-pinned;
    shrinkage always passes. ``allow_lower_bound`` acknowledges a
    program whose cost is a loud lower bound (an unknown-trip-count
    while); pinned programs default to refusing that, so a scheduling
    change that hides a loop's trip count cannot quietly deflate its
    pinned numbers.
    """

    max_flops: int | None = None
    max_hbm_bytes: int | None = None
    max_wire_bytes: int | None = None
    allow_lower_bound: bool = False
    note: str = ""


def check_cost(cost, budget: CostBudget | None) -> tuple[list[Finding], dict]:
    """Diff a program's static cost estimate against its pinned budget.

    ``cost``: analysis/cost.estimate_cost over the compiled module text.
    Returns (findings, stats); a None budget records stats without
    judging them (scripts/audit.py still prints them).
    """
    stats = {
        "flops": cost.flops,
        "hbm_bytes": cost.hbm_bytes,
        "wire_bytes": cost.wire_bytes,
        "wire_by_collective": dict(cost.wire_by_collective),
        "arithmetic_intensity": round(cost.arithmetic_intensity, 4),
        "lower_bound": cost.lower_bound,
        "unknown_trip_whiles": list(cost.unknown_trip_whiles),
        "num_partitions": cost.num_partitions,
    }
    findings: list[Finding] = []
    if budget is None:
        return findings, stats
    stats["budget"] = {
        "max_flops": budget.max_flops,
        "max_hbm_bytes": budget.max_hbm_bytes,
        "max_wire_bytes": budget.max_wire_bytes,
        "note": budget.note,
    }
    if cost.lower_bound and not budget.allow_lower_bound:
        findings.append(
            Finding(
                checker="cost",
                code="cost-lower-bound",
                severity="error",
                message=(
                    "cost estimate is only a LOWER BOUND: while loop(s) "
                    f"{list(cost.unknown_trip_whiles)} carry no static "
                    "trip count, so their bodies were counted once — the "
                    "pinned ceilings cannot certify this program; derive "
                    "the trip count or set allow_lower_bound with "
                    "reasoning"
                ),
                detail={"whiles": list(cost.unknown_trip_whiles)},
            )
        )
    for label, got, cap in (
        ("flops", cost.flops, budget.max_flops),
        ("hbm_bytes", cost.hbm_bytes, budget.max_hbm_bytes),
        ("wire_bytes", cost.wire_bytes, budget.max_wire_bytes),
    ):
        if cap is not None and got > cap:
            findings.append(
                Finding(
                    checker="cost",
                    code=f"cost-{label.replace('_', '-')}-exceeded",
                    severity="error",
                    message=(
                        f"static {label} {got:,} > pinned ceiling "
                        f"{cap:,} — the per-step {label} grew (doubled "
                        "math, upcast traffic, or an extra collective); "
                        "re-pin only if the growth is a deliberate "
                        "contract change (docs/ANALYSIS.md §7)"
                    ),
                    detail={label: got, f"max_{label}": cap},
                )
            )
    return findings, stats


# Pinned static-cost ceilings per registered audit case — the
# throughput counterpart of STABLE_MEMORY_BUDGETS. Each triple is the
# measured per-chip FLOPs / HBM-bytes-moved / collective-wire-bytes of
# the compiled program on the tiny registry models (8 virtual CPU
# devices, XLA:CPU schedule, jax 0.4.37), frozen exactly: growth in any
# number is a perf regression (doubled math, upcast traffic, extra or
# fatter collectives) until adjudicated and re-pinned; shrinkage always
# passes. The relationships BETWEEN pins are themselves claims the test
# suite re-derives from cost alone (tests/test_cost_analysis.py):
# - the q8 decode steps move FEWER HBM bytes than their f32 twins
#   (624_491 < 712_875: int8 pages are real traffic, not just a
#   smaller allocation);
# - zero2_bucketed's wire bytes EQUAL zero2's (1_147_790 both —
#   bucketing coalesces instructions, the gradient bytes on the wire
#   are conserved);
# - the speculative [slots, K+1] verify steps cost ~(K+1)x the plain
#   step's FLOPs (3_788_766 / 995_578 ≈ 3.8 at K=3: verification is
#   K+1 tokens of real work in one dispatch, not free);
# - the ddp/zero1/zero2/zero3 wire bytes match profiling/comm_model's
#   analytic ring formulas (ddp: 2·G·(N-1)/N = 765_191 at G≈437 KiB,
#   N=8).
# Wire pins are per-chip ring-transfer bytes; 0 means every collective
# in the program (if any) spans a single-member group.
# max_hbm_bytes of the 17 decode_* programs that run decode.forward is
# the jax 0.9.0 reading of the carried-cache programs (see the note
# above STABLE_MEMORY_BUDGETS); their max_flops and every other entry
# keep the older readings the paragraph above quotes.
# Re-pin procedure: docs/ANALYSIS.md §7.
STABLE_COST_BUDGETS: dict[str, CostBudget] = {
    "baseline": CostBudget(
        max_flops=183_932_936, max_hbm_bytes=169_741_764,
        max_wire_bytes=0,
    ),
    "train_guard": CostBudget(
        max_flops=185_035_563, max_hbm_bytes=171_955_291,
        max_wire_bytes=0,
    ),
    "ddp": CostBudget(
        max_flops=24_937_385, max_hbm_bytes=23_071_428,
        max_wire_bytes=765_191,
    ),
    "ddp_bf16": CostBudget(
        max_flops=25_543_593, max_hbm_bytes=24_730_316,
        max_wire_bytes=765_191,
        note="wire bytes EQUAL f32 ddp's: grads are reduced in f32 "
             "(master-weight contract) even under bf16 compute",
    ),
    "fsdp": CostBudget(
        max_flops=23_024_363, max_hbm_bytes=15_904_440,
        max_wire_bytes=1_114_638,
    ),
    "zero2": CostBudget(
        max_flops=23_024_443, max_hbm_bytes=21_446_912,
        max_wire_bytes=1_147_790,
    ),
    "fsdp_prefetch": CostBudget(
        max_flops=23_504_197, max_hbm_bytes=14_366_932,
        max_wire_bytes=1_114_862,
        note="wire ~= plain fsdp (224 B of window bookkeeping): the "
             "prefetch schedule moves WHEN gathers run, not how much",
    ),
    "zero2_bucketed": CostBudget(
        max_flops=23_024_550, max_hbm_bytes=22_520_684,
        max_wire_bytes=1_147_790,
        note="wire bytes EQUAL zero2's: bucketing coalesces 16 "
             "reduce-scatters into 2, the gradient bytes are conserved",
    ),
    "tp": CostBudget(
        max_flops=58_934_440, max_hbm_bytes=138_191_808,
        max_wire_bytes=983_046,
    ),
    "ring": CostBudget(
        max_flops=48_101_694, max_hbm_bytes=59_218_736,
        max_wire_bytes=1_245_702,
    ),
    "ulysses": CostBudget(
        max_flops=48_547_534, max_hbm_bytes=41_490_092,
        max_wire_bytes=950_790,
    ),
    "ep": CostBudget(
        max_flops=275_422_141, max_hbm_bytes=85_402_756,
        max_wire_bytes=1_441_548,
    ),
    "pipeline": CostBudget(
        max_flops=123_603_517, max_hbm_bytes=125_967_090,
        max_wire_bytes=201_228,
    ),
    "pipeline_1f1b": CostBudget(
        max_flops=312_516_369, max_hbm_bytes=205_151_114,
        max_wire_bytes=365_064,
    ),
    "decode_prefill": CostBudget(
        max_flops=1_870_946, max_hbm_bytes=572_784,
        max_wire_bytes=0,
    ),
    "decode_step": CostBudget(
        max_flops=248_741, max_hbm_bytes=77_820,
        max_wire_bytes=0,
    ),
    "zero3_decode_prefetch": CostBudget(
        max_flops=160_202, max_hbm_bytes=680_844,
        max_wire_bytes=351_750,
        allow_lower_bound=True,
        note="decode_run's token while exits early on EOS — the trip "
             "count is data-dependent, so XLA records none and the "
             "body is counted ONCE; the pin certifies the per-iteration "
             "cost shape (setup + one token step), not a full "
             "generation",
    ),
    "decode_batched_prefill": CostBudget(
        max_flops=1_875_603, max_hbm_bytes=654_733,
        max_wire_bytes=0,
    ),
    "decode_batched_step": CostBudget(
        max_flops=995_438, max_hbm_bytes=712_835,
        max_wire_bytes=0,
    ),
    "decode_batched_step_tp": CostBudget(
        max_flops=357_974, max_hbm_bytes=246_627,
        max_wire_bytes=6_144,
    ),
    "decode_paged_prefill": CostBudget(
        max_flops=1_874_550, max_hbm_bytes=679_744,
        max_wire_bytes=0,
    ),
    "decode_paged_step": CostBudget(
        max_flops=995_578, max_hbm_bytes=712_875,
        max_wire_bytes=0,
    ),
    "decode_paged_prefill_q8": CostBudget(
        max_flops=1_918_006, max_hbm_bytes=585_920,
        max_wire_bytes=0,
        note="HBM 0.53x the f32 paged prefill: int8 pages move int8 "
             "bytes; the extra flops are the quantize/dequantize math",
    ),
    "decode_paged_step_q8": CostBudget(
        max_flops=1_031_642, max_hbm_bytes=624_491,
        max_wire_bytes=0,
        note="HBM 0.57x the f32 paged step: the cache-read traffic "
             "shrinks by the page pool's 0.3125x, diluted by the "
             "unquantized weights/activations",
    ),
    "decode_batched_step_tp_q8": CostBudget(
        max_flops=360_662, max_hbm_bytes=250_723,
        max_wire_bytes=6_144,
        note="wire bytes EQUAL the f32 tp step's: the Megatron psums "
             "reduce f32 activations either way; int8 slims HBM, not "
             "the wire",
    ),
    "decode_batched_spec_step": CostBudget(
        max_flops=3_788_230, max_hbm_bytes=900_979,
        max_wire_bytes=0,
    ),
    "decode_paged_spec_step": CostBudget(
        max_flops=3_788_766, max_hbm_bytes=901_067,
        max_wire_bytes=0,
        note="~3.8x the plain paged step's flops at K=3: the [slots, "
             "K+1] verify forward is K+1 tokens of real math in one "
             "dispatch",
    ),
    "decode_batched_step_tp_spec": CostBudget(
        max_flops=1_238_374, max_hbm_bytes=357_107,
        max_wire_bytes=24_576,
        note="wire = 4x the plain tp step's 6_144: the psum payload is "
             "[slots, K+1, ...] — speculative verify widens the "
             "collective by exactly K+1",
    ),
    "decode_paged_prefill_lora": CostBudget(
        max_flops=1_977_084, max_hbm_bytes=730_949,
        max_wire_bytes=0,
    ),
    "decode_paged_step_lora": CostBudget(
        max_flops=1_046_878, max_hbm_bytes=750_799,
        max_wire_bytes=0,
    ),
    "decode_batched_step_tp_lora": CostBudget(
        max_flops=390_074, max_hbm_bytes=272_775,
        max_wire_bytes=6_144,
    ),
    "decode_paged_kv_export": CostBudget(
        max_flops=12, max_hbm_bytes=81_936,
        max_wire_bytes=0,
        note="a pure gather: ~zero flops, and the HBM bill is the pool "
             "read + page write — any math appearing here is a bug",
    ),
    "decode_paged_kv_import": CostBudget(
        max_flops=4_190, max_hbm_bytes=328_092,
        max_wire_bytes=0,
        note="a pure scatter at freshly allocated page ids; flops are "
             "the table-indexing arithmetic, not tensor math",
    ),
    "decode_paged_kv_import_q8": CostBudget(
        max_flops=4_518, max_hbm_bytes=103_176,
        max_wire_bytes=0,
        note="HBM 0.31x the f32 import: int8 pages move int8 bytes, "
             "and the zero q8-cast pin keeps it that way",
    ),
    "decode_paged_kv_import_tp": CostBudget(
        max_flops=2_142, max_hbm_bytes=164_252,
        max_wire_bytes=0,
        note="wire bytes ZERO under tensor=2: each shard scatters its "
             "own head slice — a collective here would silently "
             "multiply the handoff's wire cost",
    ),
    "ddp_pjit": CostBudget(
        max_flops=24_735_275, max_hbm_bytes=23_540_208,
        max_wire_bytes=822_535,
    ),
    "fsdp_pjit": CostBudget(
        max_flops=23_073_182, max_hbm_bytes=29_725_492,
        max_wire_bytes=3_567_767,
        note="3.2x the explicit fsdp's wire: GSPMD re-gathers per use "
             "site where the explicit schedule gathers once per layer "
             "— the quantified cost of leaving placement to the "
             "partitioner",
    ),
    "zero2_pjit": CostBudget(
        max_flops=23_108_497, max_hbm_bytes=33_532_884,
        max_wire_bytes=2_770_551,
    ),
    "tp_pjit": CostBudget(
        max_flops=58_901_672, max_hbm_bytes=137_143_324,
        max_wire_bytes=786_468,
    ),
    "ring_pjit": CostBudget(
        max_flops=47_477_847, max_hbm_bytes=39_873_764,
        max_wire_bytes=1_445_382,
    ),
    "ep_pjit": CostBudget(
        max_flops=402_948_676, max_hbm_bytes=104_013_404,
        max_wire_bytes=3_281_922,
    ),
}


def cost_budget_for(case: str) -> CostBudget:
    """The pinned STABLE_COST_BUDGETS entry for ``case``.

    KeyError (with the fix spelled out) when the case has no pin: every
    registered program must carry a cost budget, so a new program cannot
    ship with unaudited throughput resources.
    """
    try:
        return STABLE_COST_BUDGETS[case]
    except KeyError:
        raise KeyError(
            f"no pinned cost budget for registered case {case!r} — "
            "measure it (scripts/audit.py --case "
            f"{case} --only cost --json r.json, read "
            "summary.cost) and add a STABLE_COST_BUDGETS entry "
            "(docs/ANALYSIS.md §7 documents the re-pin procedure)"
        ) from None


def expected_budget(
    mesh_cfg: MeshConfig, model_cfg: ModelConfig | None = None
) -> CollectiveBudget:
    """The collective contract a (mesh, model) combination implies.

    Mirrors the strategy implementations: required ops are the collectives
    each active axis/strategy writes (or AD transposes into existence);
    everything no active axis can legitimately produce is forbidden.
    all-reduce is tolerated whenever ANY axis is active — every path
    all-reduces the scalar loss/grad-norm metrics across its axes.
    """
    required: set[str] = set()
    notes: list[str] = []

    dp_active = mesh_cfg.data > 1
    fsdp_active = mesh_cfg.fsdp > 1
    if fsdp_active and mesh_cfg.strategy == "full_shard":
        # ZeRO-3: just-in-time param all-gather; its AD transpose IS the
        # gradient reduce-scatter.
        required |= {"all-gather", "reduce-scatter"}
        notes.append("fsdp/full_shard: gather params + scatter grads")
    elif fsdp_active and mesh_cfg.strategy == "shard_grad_op":
        # ZeRO-2: grads reduce-scattered onto opt-state shards; params
        # re-materialise via a psum of disjoint slices (an all-reduce).
        required |= {"reduce-scatter"}
        notes.append("fsdp/shard_grad_op: scatter grads")
    elif fsdp_active and mesh_cfg.strategy == "shard_opt":
        # ZeRO-1: grads replicated-all-reduced like DDP.
        required |= {"all-reduce"}
        notes.append("fsdp/shard_opt: all-reduce grads")
    elif fsdp_active:  # no_shard with an fsdp axis: pure data parallelism
        required |= {"all-reduce"}
    if dp_active:
        required |= {"all-reduce"}
        notes.append("data: all-reduce grads at the accumulation boundary")
    if mesh_cfg.tensor > 1:
        # Megatron f/g conjugates: psum after every row-parallel projection.
        required |= {"all-reduce"}
        notes.append("tensor: psum at parallel-region boundaries")
    if mesh_cfg.seq > 1:
        if model_cfg is not None and model_cfg.seq_impl == "ulysses":
            required |= {"all-to-all"}
            notes.append("seq/ulysses: head<->sequence all-to-all")
        else:
            required |= {"collective-permute"}
            notes.append("seq/ring: KV ring ppermute")
    if mesh_cfg.expert > 1:
        required |= {"all-to-all"}
        notes.append("expert: token dispatch all-to-all")
    if mesh_cfg.pipe > 1:
        required |= {"collective-permute"}
        notes.append("pipe: stage-boundary shifts")

    if not required:
        return NO_COLLECTIVES

    # Scalar metrics (loss, grad_norm) are all-reduced over every active
    # axis on every path, so all-reduce can appear even when no strategy
    # requires it for gradients.
    tolerated = {"all-reduce"}
    forbidden = set(HLO_COLLECTIVES) - required - tolerated
    return CollectiveBudget(
        required=frozenset(required),
        forbidden=frozenset(forbidden),
        note="; ".join(notes),
    )


def check_async_overlap(
    pairs: list[AsyncCollective],
    min_compute: int,
) -> list[Finding]:
    """Assert every async collective start/done pair has compute scheduled
    between it (the overlap contract of the prefetch schedule).

    ``pairs``: analysis/hlo.async_collective_pairs over the compiled
    module. A pair with fewer than ``min_compute`` compute instructions
    between start and done is async in name only — the scheduler found
    nothing to hide the transfer under, so its full latency is exposed
    (error). An EMPTY pair list is reported as info, never success: sync
    backends (XLA:CPU) emit no -start/-done forms at all, and a green
    check that verified nothing would be coverage theater.
    """
    if not pairs:
        return [
            Finding(
                checker="collectives",
                code="no-async-collectives",
                severity="info",
                message=(
                    "overlap contract requested but the compiled module "
                    "schedules no async start/done pairs (sync-collective "
                    "backend, e.g. XLA:CPU) — overlap is UNVERIFIED here; "
                    "re-audit on a TPU/GPU backend for schedule evidence"
                ),
            )
        ]
    findings: list[Finding] = []
    for pair in pairs:
        if pair.compute_between < min_compute:
            findings.append(
                Finding(
                    checker="collectives",
                    code="exposed-async-collective",
                    severity="error",
                    message=(
                        f"{pair.start!r}/{pair.done!r}: only "
                        f"{pair.compute_between} compute instruction(s) "
                        f"scheduled between start and done "
                        f"(contract: >= {min_compute}) — the "
                        f"{pair.opcode} latency is exposed, not hidden"
                    ),
                    detail={
                        "opcode": pair.opcode,
                        "start": pair.start,
                        "done": pair.done,
                        "compute_between": pair.compute_between,
                        "min_compute": min_compute,
                    },
                )
            )
    return findings


def check_budget(
    found: dict[str, list[str]],
    budget: CollectiveBudget,
    *,
    classify=None,
) -> list[Finding]:
    """Diff the collectives a compiled program emits against its budget.

    ``found``: {base_opcode: [instruction names]} from
    analysis.hlo.collective_instructions. ``classify``: optional
    name -> category function (profiling.trace_analysis.classify_op);
    when given, every emitted collective instruction name must classify as
    "communication" — the guarantee that trace analysis will account for
    it (tests/test_hlo_collectives.py assertion 1).
    """
    findings: list[Finding] = []
    present = set(found)

    for op in sorted(budget.required - present):
        findings.append(
            Finding(
                checker="collectives",
                code="missing-collective",
                severity="error",
                message=(
                    f"strategy promises {op!r} but the compiled program "
                    f"never emits it (found: {sorted(present) or 'none'})"
                ),
                detail={"opcode": op, "found": sorted(present)},
            )
        )
    for op in sorted(budget.forbidden & present):
        findings.append(
            Finding(
                checker="collectives",
                code="forbidden-collective",
                severity="error",
                message=(
                    f"{op!r} appears {len(found[op])}x but the strategy "
                    "has no business emitting it"
                ),
                detail={"opcode": op, "instructions": found[op]},
            )
        )
    for op, cap in sorted(budget.max_counts.items()):
        n = len(found.get(op, []))
        if n > cap:
            findings.append(
                Finding(
                    checker="collectives",
                    code="budget-exceeded",
                    severity="error",
                    message=f"{op!r}: {n} instructions > budget of {cap}",
                    detail={
                        "opcode": op,
                        "count": n,
                        "budget": cap,
                        "instructions": found.get(op, []),
                    },
                )
            )
    if classify is not None:
        for op, names in sorted(found.items()):
            for name in names:
                cat = classify(name)
                if cat != "communication":
                    findings.append(
                        Finding(
                            checker="collectives",
                            code="unclassified-collective",
                            severity="error",
                            message=(
                                f"trace classifier labels {name!r} as "
                                f"{cat!r}, not 'communication' — trace "
                                "accounting would miscount this op"
                            ),
                            detail={"instruction": name, "category": cat},
                        )
                    )
    return findings
