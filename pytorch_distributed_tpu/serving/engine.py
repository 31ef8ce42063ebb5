"""Persistent donated-KV decode engine: the serving fast path.

The monolithic ``generate`` programs (models/decode.py) are the wrong
shape for a serving loop: the KV cache is jit-internal (re-allocated and
re-zeroed every request), every distinct prompt length compiles a fresh
prefill+loop program, and — before this PR — every sampling config change
recompiled too. ``DecodeEngine`` restructures generation into two
long-lived compiled programs, the shape TPU serving practice settles on
(Fine-Tuning and Serving Gemma on Cloud TPU; the pjit-scaling playbook —
PAPERS.md):

- ``prefill(params, prompt, prompt_len, cache, t, k, p, key)``
  runs the whole (bucket-padded) prompt and samples the first token;
- ``decode_run(params, tok, cache, pos, n, t, k, p, key)``
  runs n single-token steps in one dispatch (a fori_loop with a TRACED
  trip count — one compile covers every generation length);
- ``decode_step(...)`` is the single-step form behind ``stream()``.

Three levers, all machine-checked:

1. **Buffer donation**: the cache is ``donate_argnums``-donated through
   every program, and the engine keeps the returned buffer in a pool —
   steady-state serving allocates and zero-fills NOTHING per request.
   Reusing a dirty buffer is sound because decode's cache discipline
   (models/decode.py) masks key positions > pos and overwrites each row
   before it becomes readable; tests/test_serving.py pins it, including
   the GQA edge. Donation is verified to actually alias in the compiled
   executable (``verify_donation`` + the strict mode of
   analysis/audit.check_donation) — a silently-rejected alias would
   double-buffer the largest tensor in the server.
2. **Bounded compilation**: prompts are padded to a small set of
   ``BucketSpec`` lengths (default powers of two), so steady-state
   serving compiles O(buckets) prefill programs + ONE decode program —
   not O(requests). Sampling params are traced scalars
   (decode.sampling_scalars); only greedy-vs-sampled is static.
3. **Comm/compute overlap (ZeRO-3 mode)**: decode from full-shard
   training layouts routes the layer scan through
   ops/layer_scan.scan_layers's windowed double-buffer schedule
   (``MeshConfig.prefetch_buffers``), so layer l+1's param all-gathers
   stream in under layer l's compute — the decode-side twin of the
   explicit training path's prefetch (closes ROADMAP PR-3 follow-up (c)).

Modes (one engine per mode x config):
- plain: single device, whole params.
- tp (``mesh_cfg.tensor`` > 1): shard_map over a "tensor" mesh, Megatron
  layouts, local-head cache shards (the cache pytree is a GLOBAL array
  sharded over the head dim — 1/tp of the cache HBM per chip).
- zero3 (``mesh_cfg.fsdp`` > 1, full_shard): auto-partitioned decode in
  the ZeRO-3 training layout with the windowed gather schedule above.
TP x ZeRO-3 mixed meshes are rejected up front with a diagnostic naming
these modes (``_reject_tp_zero3_mix``); native composition is future
surface.

Two engines share this machinery:
- ``DecodeEngine`` — serial: one request (of any batch) at a time, with
  an LRU-BOUNDED dirty-cache pool across requests.
- ``BatchedDecodeEngine`` — continuous batching: a fixed pool of slot
  ROWS inside one (slots, max_len) cache, a host-side scheduler that
  admits/retires requests per row, per-row traced positions and sampling
  state, and ONE compiled decode step advancing every row per dispatch.
  See its class docstring; this is the engine that fills the batch
  dimension under real multi-tenant traffic.

Outputs are bit-equal to the monolithic reference paths for identical
requests (greedy and fixed-key sampled) — same forward, same sampler,
same key-folding schedule; padded prompt rows and pooled-buffer garbage
are masked out of every reduction. Pinned by tests/test_serving.py.

Not thread-safe: the cache pool hands the SAME buffer to concurrent
requests of one batch size. Serialise requests per engine (or shard
engines per worker).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.models import decode
from pytorch_distributed_tpu.ops.quant import quantize_decode_params
from pytorch_distributed_tpu.profiling.spans import Timers
from pytorch_distributed_tpu.serving.lifecycle import (
    ABORTED,
    DONE,
    EXPIRED,
    FAILED,
    AdmissionQueueFull,
    DispatchFailure,
    EngineSnapshot,
    RequestFailed,
    RequestResult,
)
from pytorch_distributed_tpu.serving.scheduler import (
    BATCH,
    INTERACTIVE,
    PRIORITIES,
    STANDARD,
    TIER_NAME,
    TIER_RANK,
    check_priority,
    preemption_key,
    queue_key,
)
from pytorch_distributed_tpu.serving.staged import paged_layouts
from pytorch_distributed_tpu.utils.logging import log_event

_PROGRAM_KINDS = ("prefill", "decode_run", "decode_step")
_BATCHED_PROGRAM_KINDS = ("prefill", "decode_step", "decode_spec_step")
# Disaggregation-only paged programs: gather a row's KV pages off a
# PREFILL worker's pool / scatter them into a DECODE worker's (the
# kv_handoff wire path). Never dispatched by the tick scheduler.
_KV_PROGRAM_KINDS = ("kv_export", "kv_import")
_EMPTY_DRAFT = np.zeros((0,), np.int32)


_kv_bytes_per_position = decode.kv_bytes_per_position


def _check_quant_arg(name: str, value: str) -> str:
    if value not in ("none", "int8"):
        raise ValueError(
            f"{name} must be 'none' or 'int8', got {value!r}"
        )
    return value


def _quantized_mesh_specs(cfg: ModelConfig, mesh, p_specs):
    """(quantized spec tree, quantized NamedSharding tree) for a
    weight-quantized decode params tree: kernel specs ride ``q8``,
    scale specs drop the contracting dim (ops/quant.quantized_param_specs
    — column-parallel scales shard with their channels, row-parallel
    scales replicate)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.ops.quant import quantized_param_specs

    abstract = jax.eval_shape(
        lambda k: get_model(cfg).init(k, cfg), jax.random.key(0)
    )
    q_specs = quantized_param_specs(p_specs, abstract)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), q_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return q_specs, shardings


def _spec_accept_rate(counters: dict[str, int]) -> float | None:
    """accepted/drafted over the engine's lifetime — None until the
    first draft (and forever on engines that never speculate), so a
    dashboard can tell "speculation off/idle" from "0% accepts"."""
    drafted = counters.get("drafted_tokens", 0)
    if not drafted:
        return None
    return round(counters.get("accepted_tokens", 0) / drafted, 4)


def _reject_tp_zero3_mix(mesh_cfg: MeshConfig | None, entry: str) -> None:
    """Both serving entry points reject the TP x ZeRO-3 mixed mesh with
    one diagnostic naming the supported modes (ROADMAP serving follow-up
    (c)): decoding from a mixed layout needs each gathered layer window
    re-split over the tensor axis inside the token loop — a schedule
    neither the shard_map TP path nor the auto-partitioned ZeRO-3 path
    expresses today. Full composition is future surface."""
    if mesh_cfg is not None and mesh_cfg.tensor > 1 and mesh_cfg.fsdp > 1:
        raise NotImplementedError(
            f"{entry} does not support TP x ZeRO-3 mixed-mesh decode "
            f"(got tensor={mesh_cfg.tensor}, fsdp={mesh_cfg.fsdp}). "
            "Supported modes: plain (single device / no mesh), tp "
            "(tensor-only mesh, Megatron layouts with a head-sharded KV "
            "cache), and zero3 (fsdp-only full_shard mesh, DecodeEngine "
            "only). Serve a mixed-mesh checkpoint by resharding to one "
            "of those layouts; native composition is a future PR."
        )


def _select_mode(
    cfg: ModelConfig, mesh_cfg: MeshConfig | None, *,
    entry: str, allow_zero3: bool = True,
):
    """Shared engine mode selection: (mode, mesh_cfg, n_kv,
    prefetch_buffers), with the mixed-mesh rejection applied first so
    both engines emit the same diagnostic."""
    _reject_tp_zero3_mix(mesh_cfg, entry)
    if mesh_cfg is None or mesh_cfg.num_devices == 1:
        return "plain", None, None, 0
    if mesh_cfg.tensor > 1:
        decode._validate_tp_mesh(cfg, mesh_cfg)
        return "tp", mesh_cfg, cfg.kv_heads // mesh_cfg.tensor, 0
    if not allow_zero3:
        raise NotImplementedError(
            f"{entry} supports plain and tp modes; ZeRO-3 slot-batched "
            "decode is future surface — serve ZeRO-3 layouts through "
            "DecodeEngine, or decode from a tensor-only mesh"
        )
    decode._validate_fsdp_mesh(mesh_cfg)
    return "zero3", mesh_cfg, None, mesh_cfg.prefetch_buffers


# Disaggregated-serving roles (uniform ``stats()["role"]`` vocabulary).
# ``colocated`` engines run prefill AND decode (the historic behaviour);
# ``prefill`` workers run chunked prefill only and hand finished KV
# state off; ``decode`` workers accept handoffs/adoptions and run the
# decode tick only. Role is pure host-side scheduling — every role runs
# the SAME compiled programs (plus the kv transfer programs), so pinned
# budgets and compile counts are role-invariant.
ENGINE_ROLES = ("colocated", "prefill", "decode")


def _check_role(role: str) -> str:
    if role not in ENGINE_ROLES:
        raise ValueError(
            f"role must be one of {ENGINE_ROLES}, got {role!r}"
        )
    return role


def _resolve_device(device):
    """Resolve an int device id (or a ``jax.Device``) to the Device
    object, validating it exists on this process. The single-device
    engines take ``device=`` so a serving fleet can pin each replica to
    its own chip instead of every replica landing on the default
    device; meshed engines place via ``MeshConfig.device_ids``."""
    if device is None:
        return None
    if not isinstance(device, (int, np.integer)):
        return device  # already a jax.Device
    for d in jax.devices():
        if d.id == int(device):
            return d
    raise ValueError(
        f"device id {device} not present among jax.devices() ids "
        f"{sorted(d.id for d in jax.devices())}"
    )


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Prompt-length buckets. A request of length T compiles (at most)
    the program of the smallest bucket >= T; ``()`` means exact-length
    (no padding — one compile per distinct length, the compat-shim
    behaviour)."""

    buckets: tuple[int, ...] = ()

    def __post_init__(self) -> None:
        b = tuple(self.buckets)
        if any(x <= 0 for x in b) or list(b) != sorted(set(b)):
            raise ValueError(
                f"buckets must be strictly increasing positives, got {b}"
            )
        object.__setattr__(self, "buckets", b)

    @classmethod
    def powers_of_two(
        cls, max_len: int, min_bucket: int = 128
    ) -> "BucketSpec":
        """128/256/.../max_len (first bucket = min_bucket clipped to
        max_len; max_len itself is always the last bucket so every
        admissible prompt has a home)."""
        if min_bucket <= 0 or max_len <= 0:
            raise ValueError("min_bucket and max_len must be positive")
        out = []
        b = min_bucket
        while b < max_len:
            out.append(b)
            b *= 2
        out.append(max_len)
        return cls(tuple(out))

    def bucket_for(self, length: int) -> int:
        if not self.buckets:
            return length
        for b in self.buckets:
            if b >= length:
                return b
        raise ValueError(
            f"prompt length {length} exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )


class DecodeEngine:
    """See module docstring. Construct once per (cfg, max_len, bucket
    spec, mesh); call ``generate`` / ``stream`` per request with any
    params matching ``cfg`` (params are call arguments, not engine state,
    so one engine serves many checkpoints of one architecture)."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        max_len: int,
        buckets: BucketSpec | None = None,
        mesh_cfg: MeshConfig | None = None,
        pool_caches: bool = True,
        pool_max_entries: int = 8,
        nan_guard: bool = True,
        weight_quant: str = "none",
        device: int | None = None,
    ) -> None:
        if max_len > cfg.n_ctx:
            raise ValueError(
                f"max_len {max_len} exceeds n_ctx {cfg.n_ctx}"
            )
        self.cfg = cfg
        self.max_len = int(max_len)
        self.buckets = buckets or BucketSpec()
        if self.buckets.buckets and self.buckets.buckets[-1] > max_len:
            raise ValueError(
                f"largest bucket {self.buckets.buckets[-1]} exceeds "
                f"max_len {max_len}"
            )
        self.mode, self.mesh_cfg, self._n_kv, self._prefetch_buffers = (
            _select_mode(cfg, mesh_cfg, entry="DecodeEngine")
        )
        self.device = _resolve_device(device)
        if self.device is not None and self.mode != "plain":
            raise ValueError(
                "device= pins the single-device (plain) engine to one "
                "chip; meshed modes place via MeshConfig.device_ids"
            )
        self.weight_quant = _check_quant_arg("weight_quant", weight_quant)
        if self.weight_quant != "none" and self.mode == "zero3":
            raise NotImplementedError(
                "weight_quant with ZeRO-3 decode is future surface: the "
                "windowed layer gathers move full-precision shards and "
                "re-splitting int8+scale leaves through the auto "
                "partitioner is unproven — serve quantized weights from "
                "plain or tensor-only meshes"
            )
        if self.weight_quant != "none" and cfg.n_experts:
            raise NotImplementedError(
                "weight_quant does not cover MoE expert stacks (routed "
                "expert weights need per-expert calibration surface) — "
                "quantized decode serves dense gpt2/llama configs"
            )
        if self.mode != "plain":
            (
                self._mesh, self._p_specs, self._param_shardings
            ) = decode._mesh_param_shardings(cfg, self.mesh_cfg)
            if self.weight_quant != "none":
                self._p_specs, self._param_shardings = (
                    _quantized_mesh_specs(cfg, self._mesh, self._p_specs)
                )
        # (source tree, prepared tree): weight quantization runs ONCE per
        # params tree (identity memo), not once per request.
        self._prepared: tuple[Any, Any] | None = None
        # Pool HBM high-water mark (pooled + the in-flight buffer at the
        # moment it is taken) — cache_hbm_bytes' peak figure.
        self._peak_cache_bytes = 0
        # (kind, sampled) -> jitted program. Prefill additionally
        # specialises per bucket shape through jit's own shape cache, so
        # compile_count() reads len(buckets)-many entries off ONE program.
        self._programs: dict[tuple[str, bool], Any] = {}
        # batch -> dirty-but-reusable donated cache buffer. pool_caches
        # False (the compat shims) frees the cache after each request
        # instead — a shim engine exists per (cfg, max_len, mesh) and
        # lives forever in shim_engine's cache, so pooling there would
        # pin one full-size cache per distinct request shape; a real
        # serving deployment constructs ONE engine and wants the pool.
        # The pool is LRU-BOUNDED at pool_max_entries distinct batch
        # shapes (ROADMAP serving follow-up (d)): a traffic mix cycling
        # through many batch sizes caps pooled-cache HBM at
        # pool_max_entries x max_len-cache bytes instead of growing with
        # shape diversity; the least-recently-returned shape is dropped
        # (freed by the allocator once the array is unreferenced).
        self._pool_caches = pool_caches
        if pool_max_entries < 1:
            raise ValueError(
                f"pool_max_entries must be >= 1, got {pool_max_entries}"
            )
        self._pool_max = int(pool_max_entries)
        self._cache_pool: dict[int, decode.Cache] = {}
        # Fault sentinel: every program returns a per-row non-finite-logits
        # flag; with the guard on, ``generate`` fetches it (one tiny
        # host read per REQUEST, not per token), retries a poisoned
        # request ONCE on a fresh zeroed cache, then fails loudly
        # (lifecycle.RequestFailed) instead of returning garbage tokens.
        self._nan_guard = bool(nan_guard)
        # Monotonic request counters — the serial slice of the uniform
        # ``stats()`` schema (see BatchedDecodeEngine.stats). The
        # speculative counters are part of the uniform schema too: the
        # serial engine never drafts, so they stay 0 — consumers read
        # one key set whichever engine backs a replica.
        self.counters: dict[str, int] = {
            "requests": 0, "done": 0, "failed": 0, "nan_retries": 0,
            "drafted_tokens": 0, "accepted_tokens": 0, "spec_commits": 0,
        }

    def stats(self) -> dict[str, Any]:
        """Uniform engine-state snapshot — one schema across the serial,
        batched, and paged engines (the router's admission signal reads
        it without caring which engine backs a replica). The serial
        engine has no scheduler, so the occupancy fields are the fixed
        idle values and only ``counters`` carries information; paged-only
        fields are None on non-paged engines rather than absent, so
        consumers never need hasattr probes."""
        return {
            "engine": type(self).__name__,
            "role": "colocated",
            "device_ids": self.device_ids(),
            "queue_depth": 0,
            "queue_depth_by_tier": {name: 0 for name in PRIORITIES},
            "slots": None,
            "active_rows": 0,
            "free_slots": None,
            "pool_pages": None,
            "free_pages": None,
            "pages_in_use": None,
            "session_pinned_pages": None,
            "sessions": None,
            "prefix_hit_rate": None,
            "allocatable_pages": None,
            "prefix_queries": None,
            "prefix_hits": None,
            "evictions": None,
            "kv_quant": "none",
            "paged_decode_impl": None,
            "state_step_impl": None,
            "kv_bytes_per_position": _kv_bytes_per_position(self.cfg),
            "state_bytes_per_row": decode.serving(
                self.cfg).state_bytes_per_row,
            "speculative_k": 0,
            "spec_accept_rate": _spec_accept_rate(self.counters),
            "counters": dict(self.counters),
            "timers": {},  # no scheduler tick, so no span
        }

    def device_ids(self) -> list[int]:
        """Process-local device ids this engine's programs run on —
        the placement figure ``stats()`` reports so a fleet operator
        can SEE that replicas landed on disjoint hardware."""
        if self.mode == "plain":
            d = self.device if self.device is not None else jax.devices()[0]
            return [d.id]
        return [d.id for d in self._mesh.devices.flat]

    # -- cache pool --------------------------------------------------------

    def new_cache(self, batch: int) -> decode.Cache:
        """Freshly-zeroed cache placed for this engine's mode (the pool
        bypasses this after the first request per batch size)."""
        self._bump_cache_peak(batch)
        if self.mode == "tp":
            # Global [L, B, S, Hkv, D] array sharded over the head dim:
            # each shard holds its LOCAL kv heads, matching the local
            # n_kv view forward sees inside shard_map.
            full = decode.init_cache(self.cfg, batch, self.max_len)
            return jax.device_put(full, self._cache_sharding())
        cache = decode.init_cache(
            self.cfg, batch, self.max_len, n_kv=self._n_kv
        )
        if self.device is not None:
            # Committed inputs pin the jitted programs' outputs to the
            # same chip, so one device_put at allocation places the
            # whole request's compute.
            cache = jax.device_put(cache, self.device)
        return cache

    def _cache_bytes(self, batch: int) -> int:
        return batch * self.max_len * _kv_bytes_per_position(self.cfg)

    def _bump_cache_peak(self, taken_batch: int | None = None) -> None:
        live = sum(self._cache_bytes(b) for b in self._cache_pool)
        if taken_batch is not None:
            live += self._cache_bytes(taken_batch)
        if live > self._peak_cache_bytes:
            self._peak_cache_bytes = live

    def cache_hbm_bytes(self) -> dict[str, int]:
        """Pooled KV-cache HBM: ``allocated`` = the buffers currently
        retained by the LRU pool, ``peak_in_use`` = the high-water mark
        of pooled + in-flight bytes — the serial engine's row of the
        figure every serving bench leg reports (the batched/paged
        engines' slots x max_len / pool numbers are the comparison)."""
        return {
            "allocated": sum(
                self._cache_bytes(b) for b in self._cache_pool
            ),
            "peak_in_use": self._peak_cache_bytes,
        }

    def _take_cache(self, batch: int) -> decode.Cache:
        pooled = self._cache_pool.pop(batch, None)
        if pooled is not None:
            self._bump_cache_peak(batch)
            return pooled
        return self.new_cache(batch)

    def _return_cache(self, batch: int, cache: decode.Cache) -> None:
        if not self._pool_caches:
            return
        # Most-recently-used at the end (dict preserves insertion order);
        # evict from the front once the pool exceeds its LRU bound.
        self._cache_pool.pop(batch, None)
        self._cache_pool[batch] = cache
        while len(self._cache_pool) > self._pool_max:
            self._cache_pool.pop(next(iter(self._cache_pool)))

    def _cache_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = self._cache_spec()
        return jax.tree.map(
            lambda s: NamedSharding(self._mesh, s), spec,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _cache_spec(self):
        from jax.sharding import PartitionSpec as P

        s = (
            P(None, None, None, "tensor", None)
            if self.mode == "tp"
            else P()
        )
        return {"k": s, "v": s}

    # -- program construction ---------------------------------------------

    def _forward(self, params, ids, cache, pos):
        kwargs = {}
        if self.mode == "tp":
            kwargs["tensor_axis"] = "tensor"
        elif self.mode == "zero3":
            from jax.sharding import NamedSharding, PartitionSpec as P

            replicated = NamedSharding(self._mesh, P())
            kwargs["block_transform"] = lambda bp: jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, replicated),
                bp,
            )
            kwargs["prefetch_buffers"] = self._prefetch_buffers
        return decode.forward(params, ids, self.cfg, cache, pos, **kwargs)

    def _bodies(self, sampled: bool):
        """The three raw program bodies for one greedy/sampled variant.
        Sampling scalars are always in the signature (greedy programs
        trace-and-drop them) so every program keys the same way. Every
        body returns a traced NaN/Inf sentinel next to its tokens
        (``decode.nonfinite_rows`` over the sampled-position logits):
        elementwise + one reduction, no collectives — the registry
        budgets for these programs are unchanged by it."""

        def prefill(params, prompt, prompt_len, cache,
                    temperature, top_k, top_p, key):
            logits, cache = self._forward(params, prompt, cache, 0)
            last = jax.lax.dynamic_slice_in_dim(
                logits, prompt_len - 1, 1, axis=1
            )[:, 0]
            tok = decode.sample_token(
                last, sampled, temperature, key, top_k, top_p
            )
            return tok, decode.nonfinite_rows(last), cache

        def decode_run(params, tok, cache, pos, n_steps,
                       temperature, top_k, top_p, key):
            out = jnp.zeros((tok.shape[0], self.max_len), jnp.int32)
            bad = jnp.zeros((tok.shape[0],), jnp.bool_)

            def step(i, carry):
                out, bad, cache, tok = carry
                logits, cache = self._forward(
                    params, tok[:, None], cache, pos + i
                )
                last = logits[:, -1]
                nxt = decode.sample_token(
                    last, sampled, temperature,
                    jax.random.fold_in(key, i), top_k, top_p,
                )
                bad = bad | decode.nonfinite_rows(last)
                return out.at[:, i].set(nxt), bad, cache, nxt

            out, bad, cache, _ = jax.lax.fori_loop(
                0, n_steps, step, (out, bad, cache, tok)
            )
            return out, bad, cache

        def decode_step(params, tok, cache, pos,
                        temperature, top_k, top_p, key):
            logits, cache = self._forward(params, tok[:, None], cache, pos)
            last = logits[:, -1]
            tok = decode.sample_token(
                last, sampled, temperature, key, top_k, top_p
            )
            return tok, decode.nonfinite_rows(last), cache

        return {
            "prefill": prefill,
            "decode_run": decode_run,
            "decode_step": decode_step,
        }

    # The cache's positional index in each program signature — the
    # donate_argnums every mode passes and the donation audit verifies.
    CACHE_ARGNUM = {"prefill": 3, "decode_run": 2, "decode_step": 2}

    def program(self, kind: str, sampled: bool):
        """The jitted program for (kind, greedy/sampled), built lazily.
        Public so the audit registry (analysis/registry.py) and tests can
        lower/compile the exact programs the engine dispatches."""
        if kind not in _PROGRAM_KINDS:
            raise KeyError(f"unknown program kind {kind!r}")
        prog = self._programs.get((kind, sampled))
        if prog is not None:
            return prog
        body = self._bodies(sampled)[kind]
        donate = (self.CACHE_ARGNUM[kind],)
        if self.mode == "plain":
            prog = jax.jit(body, donate_argnums=donate)
        elif self.mode == "tp":
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_tpu.utils.compat import shard_map

            cache_spec = self._cache_spec()
            # Everything but the params and the head-sharded cache is
            # replicated; signatures per _bodies.
            specs = {
                "prefill": (
                    self._p_specs, P(), P(), cache_spec, P(), P(), P(), P()
                ),
                "decode_run": (
                    self._p_specs, P(), cache_spec,
                    P(), P(), P(), P(), P(), P(),
                ),
                "decode_step": (
                    self._p_specs, P(), cache_spec, P(), P(), P(), P(), P()
                ),
            }[kind]
            smapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=specs,
                out_specs=(P(), P(), cache_spec),
                check_vma=True,
            )
            prog = jax.jit(smapped, donate_argnums=donate)
        else:  # zero3
            from jax.sharding import NamedSharding, PartitionSpec as P

            replicated = NamedSharding(self._mesh, P())
            n_args = {"prefill": 8, "decode_run": 9, "decode_step": 8}[kind]
            in_sh = [replicated] * n_args
            in_sh[0] = self._param_shardings
            prog = jax.jit(
                body,
                in_shardings=tuple(in_sh),
                out_shardings=(replicated, replicated, replicated),
                donate_argnums=donate,
            )
        self._programs[(kind, sampled)] = prog
        return prog

    def _place_params(self, params):
        if self.weight_quant != "none":
            # Quantize ONCE per params tree (identity memo — "weights
            # quantized at engine build", with params staying call
            # arguments), then place the int8+scale tree.
            if self._prepared is None or self._prepared[0] is not params:
                q = quantize_decode_params(params)
                if self.mode != "plain":
                    q = jax.device_put(q, self._param_shardings)
                elif self.device is not None:
                    q = jax.device_put(q, self.device)
                self._prepared = (params, q)
            return self._prepared[1]
        if self.mode == "plain":
            if self.device is None:
                return params
            # Pin once per params tree (identity memo): committed params
            # + committed cache put every program output on self.device.
            if self._prepared is None or self._prepared[0] is not params:
                self._prepared = (
                    params, jax.device_put(params, self.device)
                )
            return self._prepared[1]
        # No-op when already placed, so repeat calls pay nothing.
        return jax.device_put(params, self._param_shardings)

    # -- request API -------------------------------------------------------

    def _request_setup(self, prompt, max_new_tokens, temperature,
                       top_k, top_p):
        # Budget overflow (prompt + max_new > max_len) is rejected by
        # decode._check_sample_args at every entry before this runs.
        prompt = jnp.asarray(prompt)
        b, tp = prompt.shape
        bucket = self.buckets.bucket_for(tp)
        padded = (
            prompt
            if bucket == tp
            else jnp.pad(prompt, ((0, 0), (0, bucket - tp)))
        )
        t, k, p = decode.sampling_scalars(
            temperature, top_k, top_p, self.cfg.vocab_size
        )
        return prompt, padded, b, tp, t, k, p

    def generate(
        self,
        params,
        prompt: jax.Array,  # [B, Tp] int
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        key: jax.Array | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
    ) -> jax.Array:
        """Serve one request: returns [B, Tp + max_new_tokens] — the same
        tokens the monolithic reference produces for this request. With
        ``nan_guard`` (default), non-finite logits anywhere in the
        request retry it ONCE on a fresh zeroed cache, then raise
        ``lifecycle.RequestFailed`` — garbage tokens never escape."""
        key = decode._check_sample_args(
            prompt, max_new_tokens, temperature, key, max_len=self.max_len
        )
        prompt, padded, b, tp, t, k, p = self._request_setup(
            prompt, max_new_tokens, temperature, top_k, top_p
        )
        sampled = temperature > 0
        params = self._place_params(params)
        self.counters["requests"] += 1
        for attempt in range(2 if self._nan_guard else 1):
            out, bad = self._generate_once(
                params, prompt, padded, b, tp, max_new_tokens, sampled,
                t, k, p, key, fresh_cache=attempt > 0,
            )
            if not self._nan_guard or not bool(np.asarray(bad).any()):
                self.counters["done"] += 1
                return out
            # Poisoned: drop the (pooled) buffer this request ran on and
            # retry once from a fresh zeroed allocation — the one failure
            # mode the masking discipline cannot absolve is a transient
            # corruption inside the request's own live rows.
            self._cache_pool.pop(b, None)
            self.counters["nan_retries"] += 1
            log_event(
                "nan_detected", engine="serial", batch=b,
                attempt=attempt, prompt_len=tp,
            )
        self.counters["failed"] += 1
        raise RequestFailed(
            "non-finite logits persisted after one fresh-cache retry "
            f"(batch={b}, prompt_len={tp}): the model/params produce "
            "NaN/Inf for this input — refusing to return garbage tokens"
        )

    def _generate_once(self, params, prompt, padded, b, tp,
                       max_new_tokens, sampled, t, k, p, key, *,
                       fresh_cache: bool):
        """One full prefill + decode_run attempt. Returns (tokens, bad)
        where ``bad`` is the device-side [B] non-finite sentinel OR-ed
        over every step of the request."""
        cache = self.new_cache(b) if fresh_cache else self._take_cache(b)
        plen = jnp.asarray(tp, jnp.int32)

        # A failed dispatch DROPS the buffer instead of pooling it: once
        # a program was dispatched its donated input is consumed whether
        # or not the call succeeded, so returning it would poison the
        # pool with a deleted array; the next request simply re-allocates
        # (the cost a healthy pool avoids, paid only after a failure).
        try:
            tok, bad, cache = self.program("prefill", sampled)(
                params, padded, plen, cache, t, k, p, key
            )
            pieces = [prompt.astype(jnp.int32), tok[:, None]]
            n = max_new_tokens - 1
            if n > 0:
                out, bad_run, cache = self.program("decode_run", sampled)(
                    params, tok, cache, plen, jnp.asarray(n, jnp.int32),
                    t, k, p, key,
                )
                pieces.append(out[:, :n])
                bad = jnp.logical_or(bad, bad_run)
        except BaseException:
            cache = None
            raise
        finally:
            if cache is not None:
                self._return_cache(b, cache)
        return jnp.concatenate(pieces, axis=1), bad

    def stream(
        self,
        params,
        prompt: jax.Array,  # [B, Tp] int
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        key: jax.Array | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
    ):
        """Generator of [B] int32 token arrays, one per ``decode_step``
        dispatch — the streaming form of ``generate`` (identical tokens:
        same programs modulo the fused loop, same key folding). The cache
        buffer returns to the pool when the generator finishes or is
        closed. With ``nan_guard``, a poisoned step raises
        ``lifecycle.RequestFailed`` immediately — a stream cannot retry
        transparently (tokens already escaped to the client), so the
        client resubmits; the per-step sentinel fetch costs nothing extra
        (streaming clients fetch every token anyway)."""
        key = decode._check_sample_args(
            prompt, max_new_tokens, temperature, key, max_len=self.max_len
        )
        prompt, padded, b, tp, t, k, p = self._request_setup(
            prompt, max_new_tokens, temperature, top_k, top_p
        )
        sampled = temperature > 0
        params = self._place_params(params)
        cache = self._take_cache(b)
        plen = jnp.asarray(tp, jnp.int32)

        self.counters["requests"] += 1

        def _guard(bad):
            if self._nan_guard and bool(np.asarray(bad).any()):
                # Poisoned buffers never rejoin the pool.
                self.counters["failed"] += 1
                raise RequestFailed(
                    "non-finite logits mid-stream (batch="
                    f"{b}, prompt_len={tp}): aborting the stream — "
                    "resubmit via generate() for the fresh-cache retry"
                )

        # Same drop-on-dispatch-failure rule as generate(); an early
        # generator close (GeneratorExit at a yield) is NOT a failed
        # dispatch — `cache` is the last returned buffer and goes back
        # to the pool.
        try:
            tok, bad, cache = self.program("prefill", sampled)(
                params, padded, plen, cache, t, k, p, key
            )
            _guard(bad)
            yield tok
            step = self.program("decode_step", sampled)
            for i in range(max_new_tokens - 1):
                tok, bad, cache = step(
                    params, tok, cache, jnp.asarray(tp + i, jnp.int32),
                    t, k, p, jax.random.fold_in(key, i),
                )
                _guard(bad)
                yield tok
            self.counters["done"] += 1
        except GeneratorExit:
            raise
        except BaseException:
            cache = None
            raise
        finally:
            if cache is not None:
                self._return_cache(b, cache)

    # -- introspection -----------------------------------------------------

    def compile_count(self) -> int:
        """Total compiled executables across the engine's programs (the
        number a mixed-length request stream is asserted against:
        n_buckets prefills + 1 decode program per greedy/sampled mode)."""
        return sum(p._cache_size() for p in self._programs.values())

    def example_args(self, kind: str, params, *, batch: int = 1,
                     prompt_len: int | None = None, sampled: bool = True):
        """Example argument tuple for (lowering/auditing) ``kind`` — the
        shapes ``generate`` dispatches with."""
        tp = prompt_len or min(
            self.buckets.buckets[0] if self.buckets.buckets else 4,
            self.max_len - 1,
        )
        bucket = self.buckets.bucket_for(tp)
        t, k, p = decode.sampling_scalars(
            0.8 if sampled else 0.0, None, None, self.cfg.vocab_size
        )
        cache = self.new_cache(batch)
        key = jax.random.key(0)
        plen = jnp.asarray(tp, jnp.int32)
        prompt = jnp.zeros((batch, bucket), jnp.int32)
        tok = jnp.zeros((batch,), jnp.int32)
        if kind == "prefill":
            return (params, prompt, plen, cache, t, k, p, key)
        if kind == "decode_run":
            return (
                params, tok, cache, plen, jnp.asarray(2, jnp.int32),
                t, k, p, key,
            )
        if kind == "decode_step":
            return (params, tok, cache, plen, t, k, p, key)
        raise KeyError(f"unknown program kind {kind!r}")

    def verify_donation(self, params, *, batch: int = 1,
                        sampled: bool = True) -> dict[str, dict]:
        """Prove the KV cache actually aliases in/out of every engine
        program: lower + compile each (without running) and check the
        compiled module's input_output_alias map covers every cache leaf.
        Raises RuntimeError naming the program otherwise — a silently
        rejected donation would double-buffer the cache on every step.
        Returns {kind: alias stats} for reporting."""
        from pytorch_distributed_tpu.analysis.audit import check_donation

        stats_all: dict[str, dict] = {}
        for kind in _PROGRAM_KINDS:
            args = self.example_args(
                kind, params, batch=batch, sampled=sampled
            )
            compiled = self.program(kind, sampled).lower(*args).compile()
            findings, stats = check_donation(
                compiled.as_text(), args, (self.CACHE_ARGNUM[kind],),
                strict=True,
            )
            stats_all[kind] = stats
            if findings:
                raise RuntimeError(
                    f"engine program {kind!r} ({self.mode}): donated KV "
                    "cache does not fully alias in the compiled "
                    f"executable — {findings[0].message}"
                )
        return stats_all


@dataclasses.dataclass
class _Stamps:
    """Engine-clock instants of one request, shared by its queue entry
    and its rows (a resumed or preempted request keeps its first ones):
    they feed the ``queue_wait`` / ``request.*`` timers and the
    ``retire`` line."""

    submit: float
    row: float | None = None  # first given a row
    first_token: float | None = None  # first generated token


@dataclasses.dataclass
class _Pending:
    """A queued request (host-side): everything the prefill dispatch
    needs, encoded once at submit time. The same record doubles as a
    RESUME entry after a fault (NaN quarantine, dispatch failure, engine
    replay): ``gen`` then holds the clean tokens generated before the
    fault, and admission prefills the whole prompt+gen prefix — with
    ``prefill_keydata`` pre-folded on the host to the prefix's position
    in the per-request fold schedule, so the continuation's draws are
    bit-identical to an undisturbed run."""

    rid: int
    prompt: np.ndarray  # [Tp] int32
    bucket: int
    max_new: int  # TOTAL new-token budget (not remaining)
    eos_id: int | None
    greedy: bool
    t: float
    k: int
    p: float
    keydata: np.ndarray  # base key-impl uint32 words (decode folds these)
    prefill_keydata: np.ndarray  # key for the admission prefill's draw
    deadline: float | None = None  # engine-clock absolute deadline
    gen: list = dataclasses.field(default_factory=list)  # resume prefix
    retries: int = 0  # fault-resume count (dispatch failures)
    nan_retried: bool = False  # quarantine: one retry, then FAILED
    # Workload-scenario fields (serving/scheduler.py / session.py /
    # adapters.py): the SLO tier rank, the session a turn belongs to,
    # how many tokens of its prompt are a resubmitted transcript (the
    # session hit-rate denominator), and the row's tenant adapter slot.
    tier: int = TIER_RANK[STANDARD]
    session: int | None = None
    resub_len: int = 0
    tenant_slot: int = 0
    # Field order forces a default; ``submit`` always fills it.
    stamps: _Stamps | None = None


@dataclasses.dataclass
class _Slot:
    """One occupied row of the slot batch (host-side scheduler state)."""

    rid: int
    prompt: np.ndarray
    max_new: int
    eos_id: int | None
    pos: int  # tokens in the row's cache = next KV write offset
    fold: int  # fold_in counter for the row's NEXT sampled draw
    generated: list
    greedy: bool
    t: float
    k: int
    p: float
    keydata: np.ndarray
    deadline: float | None = None
    retries: int = 0
    nan_retried: bool = False
    tier: int = TIER_RANK[STANDARD]
    session: int | None = None
    resub_len: int = 0
    tenant_slot: int = 0
    stamps: _Stamps | None = None  # the request's, from its ``_Pending``


class BatchedDecodeEngine:
    """Continuous batching: slot-scheduled multi-request decode.

    ``DecodeEngine`` serves one request shape at a time — under real
    traffic the batch dimension idles while requests queue. This engine
    keeps ONE long-lived ``(slots, max_len)`` KV cache whose rows are
    independent requests at unrelated depths: a host-side scheduler
    admits queued prompts into free rows (bucketed per-row prefill, or
    one batched prefill when several arrivals share a bucket), a single
    compiled ``decode_step`` advances ALL rows one token per dispatch,
    and finished rows retire without touching their neighbours. Every
    per-row quantity — position, fold counter, greedy flag,
    temperature/top_k/top_p, PRNG key — is a TRACED [slots] operand, so
    admissions, retirements, sampling-config changes, and any
    active-row pattern reuse the same executables: steady-state serving
    is zero-recompile BY CONSTRUCTION (shapes never change — the pjit
    fixed-shape compilation discipline), and the collective count of the
    TP program is invariant to how many rows are active (pinned in the
    audit registry).

    Soundness of row reuse is the PR-4 dirty-cache discipline at ROW
    granularity: a retired row's K/V stays in place; the next admission
    prefills over it, and per-row masking (``decode._cached_attention``
    with a [B] pos vector) guarantees no row ever reads cache positions
    past its own write point — including the GQA head-repeat edge
    (tests/test_serving_batched.py).

    The decode program is deliberately OBLIVIOUS to which rows are
    active: free rows compute garbage that the host discards. Gating
    them with a mask would save nothing (the shapes are fixed) and would
    make program behaviour depend on activity — exactly what the
    zero-recompile and collective-count contracts forbid. ``active`` is
    therefore host-side scheduler state, not a program operand.

    Modes: plain and tp (head-sharded global cache — 1/tp of the cache
    HBM per chip). ZeRO-3 slot batching and TP x ZeRO-3 stay rejected
    with explicit diagnostics (``_select_mode``). MoE configs are
    rejected: expert capacity couples rows through the dispatch (a busy
    neighbour could evict a row's tokens), breaking the per-row
    independence this engine is built on.

    Unlike the serial engine there is no greedy/sampled program split:
    one batch serves both kinds of row, so greedy is a traced per-row
    flag and every row draws: from its top ``decode.SAMPLE_CAP`` tokens,
    or over the whole vocabulary sorted when some sampled row asks for
    more, a branch inside the same program (see
    ``decode.sample_token_rows``). Program count: ONE decode_step shape
    + (buckets x prefill group sizes) prefill shapes — compile_count()
    is asserted flat across admit/retire churn in tests.

    **Batched speculative decoding** (``speculative_k=K`` > 0): decode
    is bandwidth-bound — every tick streams the whole model to emit ONE
    token per row — so each tick instead drafts up to K tokens per
    GREEDY row host-side (prompt-lookup n-gram match over the row's
    tokens-so-far, ``models/speculative.prompt_lookup_draft``; or the
    engine's ``draft_hook``) and verifies ALL rows' drafts in ONE
    [slots, K+1] ``decode_spec_step`` forward. Accept lengths are
    per-row TRACED outputs (``decode.speculative_accept``), so rows
    accepting 0..K tokens share one compiled program — the decode tick
    count drops by the mean accepted length while every contract above
    (zero steady compiles, strict donation, rows-invariant collectives)
    holds verbatim. Greedy speculative output is TOKEN-EQUAL to the
    non-speculative engine by construction: the verification forward is
    the ground truth, drafts only change speed. Sampled rows ride the
    same program with zero drafts (their lane-0 draw bit-matches the
    plain step; exact sampled speculation needs rejection-sampling
    corrections — out of scope). Drafting LOSES on low-repetition
    streams: they pay the (K+1)-wide verify for ~0 accepts (by how much
    is not measured on the chip).

    Not thread-safe (single dispatcher per engine); requests are
    single-sequence (one row each — batch your own beams as separate
    requests).

    **Request lifecycle + fault model** (docs/ROBUSTNESS.md): every
    request reaches exactly one terminal ``RequestResult`` state —
    DONE / FAILED / ABORTED / EXPIRED — delivered via ``pop_result``.
    Per-request deadlines (``submit(timeout_s=...)``) expire queued AND
    mid-decode requests with their clean partial output; ``abort(rid)``
    retires a slot row mid-decode as pure host bookkeeping (traced
    shapes untouched — no recompile, neighbours unperturbed); the
    admission queue is bounded (``queue_limit`` + reject-loudly or
    block-with-timeout backpressure). Both compiled programs return a
    traced NaN/Inf logit sentinel next to their tokens; a poisoned row
    is QUARANTINED (freed, requeued, its prefix re-prefilled over a
    fresh row — neighbours keep decoding untouched), retried once, then
    FAILED. A failed/dropped dispatch consumed the donated cache, so
    EVERY in-flight row converts to a resume entry (tokens-so-far +
    pre-folded PRNG schedule) and is re-prefilled on the next tick —
    bounded by per-request ``request_retries`` and engine-level
    consecutive ``dispatch_retries`` with exponential backoff.
    ``snapshot()`` captures that same host state at any tick boundary;
    ``restore()`` on a rebuilt engine after device loss re-prefills
    every in-flight request and continues token-identically. The
    deterministic fault-injection harness (serving/chaos.py) drives all
    of these paths in tests/test_chaos.py.
    """

    # The donated cache's positional index in each program signature.
    CACHE_ARGNUM = {"prefill": 4, "decode_step": 2, "decode_spec_step": 2}

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        slots: int,
        max_len: int,
        buckets: BucketSpec | None = None,
        mesh_cfg: MeshConfig | None = None,
        prefill_groups: tuple[int, ...] | None = None,
        queue_limit: int | None = None,
        backpressure: str = "reject",
        request_retries: int = 3,
        dispatch_retries: int | None = 2,
        retry_backoff_s: float = 0.05,
        clock=None,
        sleep=None,
        weight_quant: str = "none",
        adapters=None,
        speculative_k: int = 0,
        spec_ngram: int = 2,
        draft_hook=None,
        device: int | None = None,
    ) -> None:
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len > cfg.n_ctx:
            raise ValueError(f"max_len {max_len} exceeds n_ctx {cfg.n_ctx}")
        if cfg.n_experts:
            # (a dropless layer, ops/moe.moe_dropless, has no capacity and
            # is served: n_experts is 0 there)
            raise NotImplementedError(
                "BatchedDecodeEngine does not serve capacity-routed MoE "
                "configs (n_experts > 0): expert "
                "capacity couples batch rows through the dispatch, so a "
                "row's output would depend on its neighbours — use the "
                "serial DecodeEngine for MoE decode"
            )
        if not decode.serving(cfg).dense_cache and not isinstance(
            self, PagedBatchedDecodeEngine
        ):
            raise NotImplementedError(
                f"the {cfg.family} family is served from a paged pool "
                "only (a latent pool, pages beside per-row state, or two "
                "groups of pages): "
                "serve it through PagedBatchedDecodeEngine "
                "(decode.init_cache has no dense layout for it)"
            )
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.buckets = buckets or BucketSpec()
        if self.buckets.buckets and self.buckets.buckets[-1] > max_len:
            raise ValueError(
                f"largest bucket {self.buckets.buckets[-1]} exceeds "
                f"max_len {max_len}"
            )
        if prefill_groups is None:
            # Powers of two up to the slot count: a burst of n same-bucket
            # arrivals pads to the next group size, so prefill compiles
            # O(buckets x log slots) shapes, not O(buckets x slots).
            groups = []
            g = 1
            while g < self.slots:
                groups.append(g)
                g *= 2
            groups.append(self.slots)
            prefill_groups = tuple(groups)
        pg = tuple(sorted(set(int(g) for g in prefill_groups)))
        if not pg or pg[0] < 1 or pg[-1] < self.slots:
            raise ValueError(
                f"prefill_groups must be positive and cover the slot "
                f"count {self.slots}, got {prefill_groups}"
            )
        self._groups = pg
        self.mode, self.mesh_cfg, self._n_kv, _ = _select_mode(
            cfg, mesh_cfg, entry="BatchedDecodeEngine", allow_zero3=False
        )
        self.device = _resolve_device(device)
        if self.device is not None and self.mode != "plain":
            raise ValueError(
                "device= pins the single-device (plain) engine to one "
                "chip; meshed modes place via MeshConfig.device_ids"
            )
        # Disaggregation role: the dense engine always runs colocated
        # (KV handoff ships PAGES — PagedBatchedDecodeEngine overrides
        # this with its role= knob); the attribute exists here so the
        # uniform stats() schema carries one key set for every engine.
        self.role = "colocated"
        # Per-row speculative decoding (batched prompt-lookup — ROADMAP
        # direction 3): with speculative_k=K > 0 every decode tick
        # drafts up to K tokens per GREEDY row host-side (zero model
        # cost; ``draft_hook(tokens_so_far, k) -> drafts`` overrides the
        # n-gram lookup, e.g. for a small draft model later) and ONE
        # batched ``decode_spec_step`` forward verifies all rows'
        # drafts with per-row TRACED accept lengths — rows accepting
        # 0..K tokens share one compiled program, so the zero-steady-
        # compile / strict-donation / rows-invariant-collective
        # contracts survive unchanged. K=0 keeps the exact pre-spec
        # programs (decode_spec_step is never built). Sampled rows ride
        # the same program with zero drafts: distribution-exact sampled
        # speculation needs rejection-sampling corrections, which stay
        # out of scope (models/speculative.py).
        if speculative_k < 0:
            raise ValueError(
                f"speculative_k must be >= 0, got {speculative_k} "
                "(0 disables speculation)"
            )
        if speculative_k >= max_len:
            raise ValueError(
                f"speculative_k ({speculative_k}) must be < max_len "
                f"({max_len}): the verify window is k+1 tokens wide and "
                "has to fit a row's cache extent"
            )
        if spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {spec_ngram}")
        if draft_hook is not None and not callable(draft_hook):
            raise ValueError(
                "draft_hook must be callable: (tokens_so_far [n] int32, "
                "k) -> up to k draft tokens"
            )
        self.speculative_k = int(speculative_k)
        self.spec_ngram = int(spec_ngram)
        self._draft_hook = draft_hook
        self.weight_quant = _check_quant_arg("weight_quant", weight_quant)
        # Multi-tenant LoRA (serving/adapters.py): when a registry is
        # attached, every dispatch carries TWO extra traced operands —
        # the stacked adapter tree and a [B] tenant-slot vector — so the
        # program SIGNATURES differ from the adapter-less engine (built
        # once, at construction; registration later changes values,
        # never shapes, hence never programs). No registry = the exact
        # pre-LoRA programs, so the existing audit pins are untouched.
        if adapters is not None and adapters.cfg != cfg:
            raise ValueError(
                "adapters= was built for a different ModelConfig than "
                "this engine serves — one registry per architecture "
                "(build it once and share it across replicas)"
            )
        self.adapters = adapters
        if self.mode == "tp":
            (
                self._mesh, self._p_specs, self._param_shardings
            ) = decode._mesh_param_shardings(cfg, self.mesh_cfg)
            if self.weight_quant != "none":
                self._p_specs, self._param_shardings = (
                    _quantized_mesh_specs(cfg, self._mesh, self._p_specs)
                )
        self._programs: dict[str, Any] = {}
        # ONE cache for the engine's whole life, donated through every
        # dispatch — HBM is bounded at exactly one (slots, max_len) cache
        # by construction (no pool to bound). None = not yet allocated,
        # or dropped after a failed dispatch (the donated input is
        # consumed either way; the next dispatch re-allocates zeros and
        # per-row masking makes the lost garbage irrelevant — but the
        # in-flight rows lost their K/V, so a failure aborts them).
        self._cache: decode.Cache | None = None
        self._key_words = np.asarray(
            jax.random.key_data(jax.random.key(0))
        ).shape[-1]
        self._queue: collections.deque[_Pending] = collections.deque()
        self._slots: list[_Slot | None] = [None] * self.slots
        self._next_rid = 0
        # (source tree, placed tree): _place_params runs once per
        # scheduler tick — one jax.device_put tree traversal per TOKEN
        # without this identity memo (the serial engine pays it once per
        # request; holding the source keeps its id from being recycled).
        self._placed: tuple[Any, Any] | None = None
        self.results: dict[int, RequestResult] = {}

        # -- robustness layer (see class docstring) ---------------------
        if backpressure not in ("reject", "block"):
            raise ValueError(
                f"backpressure must be 'reject' or 'block', got "
                f"{backpressure!r}"
            )
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.queue_limit = queue_limit
        self.backpressure = backpressure
        self.request_retries = int(request_retries)
        self.dispatch_retries = dispatch_retries
        self.retry_backoff_s = float(retry_backoff_s)
        # Injectable time sources: the chaos harness (serving/chaos.py)
        # substitutes a VirtualClock so deadlines, backoff, and slow-tick
        # faults are DETERMINISTIC; production uses the monotonic clock.
        self._clock = clock or time.monotonic
        self._sleep = sleep or time.sleep
        self._injector = None  # serving/chaos.FaultInjector (or None)
        self._ticks = 0
        self._fail_streak = 0  # consecutive failed dispatches
        # Prefill shapes the engine may dispatch: the user buckets plus
        # max_len — fault-resume prefixes (prompt + tokens-so-far) can
        # exceed the largest PROMPT bucket, and the extra bucket keeps
        # them inside the warmed, finite compile set (fresh submissions
        # still obey the user BucketSpec contract unchanged).
        pb = tuple(self.buckets.buckets)
        if pb and pb[-1] < self.max_len:
            pb = pb + (self.max_len,)
        self._prefill_buckets = pb  # () = exact-length mode
        # Spans of the tick and the request timers (profiling/spans.py);
        # ``stats()`` serves a snapshot beside the counters.
        self.timers = Timers()
        # Monotonic event counters (terminal states + fault/recovery
        # tallies). The point-in-time scheduler view lives in ``stats()``
        # — the router's admission signal — which embeds a copy of these.
        self.counters: dict[str, int] = {
            "done": 0, "failed": 0, "aborted": 0, "expired": 0,
            "nan_quarantines": 0, "dispatch_failures": 0, "resumes": 0,
            "cache_allocs": 0,
            # Speculation (monotonic; 0 forever when speculative_k=0):
            # drafted = lanes offered to the verifier, accepted = extra
            # tokens committed beyond the one a plain tick yields,
            # spec_commits = row-ticks that went through the verify
            # path (the mean-accepted-length denominator).
            "drafted_tokens": 0, "accepted_tokens": 0, "spec_commits": 0,
        }

    # -- cache -------------------------------------------------------------

    def _new_cache(self) -> decode.Cache:
        self.counters["cache_allocs"] += 1
        if self.mode == "tp":
            full = decode.init_cache(self.cfg, self.slots, self.max_len)
            from jax.sharding import NamedSharding, PartitionSpec as P

            spec = P(None, None, None, "tensor", None)
            sharding = jax.tree.map(
                lambda s: NamedSharding(self._mesh, s),
                {"k": spec, "v": spec},
                is_leaf=lambda x: isinstance(x, P),
            )
            return jax.device_put(full, sharding)
        cache = decode.init_cache(
            self.cfg, self.slots, self.max_len, n_kv=self._n_kv
        )
        if self.device is not None:
            # Committed inputs pin every jitted program's outputs to
            # the same chip — one device_put per cache alloc places the
            # engine's whole steady-state compute.
            cache = jax.device_put(cache, self.device)
        return cache

    def _take_cache(self) -> decode.Cache:
        cache, self._cache = self._cache, None
        return cache if cache is not None else self._new_cache()

    # -- programs ----------------------------------------------------------

    def _forward(self, params, ids, cache, pos, lora=None):
        kwargs = {}
        if self.mode == "tp":
            kwargs["tensor_axis"] = "tensor"
        if lora:
            kwargs["lora"] = lora
        return decode.forward(params, ids, self.cfg, cache, pos, **kwargs)

    def _bodies(self):
        """The two raw program bodies. All sampling state is per-row and
        traced; ``rows``/``pos``/``folds`` are traced index vectors, so
        one compiled shape covers every admission/retirement pattern.
        Both return a [B] traced non-finite-logits sentinel
        (``decode.nonfinite_rows`` over the sampled position) — the
        scheduler quarantines flagged rows; elementwise + one reduction,
        so the pinned collective budgets (registry:
        decode_batched_step_tp all-reduce=2) are untouched by it.

        With an adapter registry attached, both bodies take two trailing
        operands — the stacked LoRA tree and the [B] tenant-slot vector
        (``*lora``) — applied inside ``decode.forward`` as per-row
        deltas; without one the signatures are byte-identical to the
        pre-LoRA engine."""

        def prefill(params, prompts, plens, rows, cache,
                    greedy, t, k, p, keydata, *lora):
            # Gather the target rows' (dirty) segments, run the normal
            # prefill forward over them at pos 0, scatter back. Padded
            # group entries duplicate row index AND data, so the
            # overlapping scatter writes are identical (deterministic).
            seg = {kk: vv[:, rows] for kk, vv in cache.items()}
            logits, seg = self._forward(params, prompts, seg, 0, lora)
            last = jnp.take_along_axis(
                logits, (plens - 1)[:, None, None], axis=1
            )[:, 0]
            keys = jax.random.wrap_key_data(keydata)
            tok = decode.sample_token_rows(last, greedy, t, keys, k, p)
            cache = {
                kk: cache[kk].at[:, rows].set(seg[kk]) for kk in cache
            }
            return tok, decode.nonfinite_rows(last), cache

        def decode_step(params, toks, cache, pos, folds,
                        greedy, t, k, p, keydata, *lora):
            logits, cache = self._forward(
                params, toks[:, None], cache, pos, lora
            )
            last = logits[:, -1]
            keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(keydata), folds
            )
            tok = decode.sample_token_rows(last, greedy, t, keys, k, p)
            return tok, decode.nonfinite_rows(last), cache

        def decode_spec_step(params, toks, cache, pos, folds,
                             greedy, t, k, p, keydata, n_draft, *lora):
            # ``toks`` [B, K+1]: lane 0 = each row's last committed
            # token, lanes 1..K = host drafts (lane-padded; n_draft [B]
            # marks the valid count). ONE forward verifies every row's
            # window; per-row accept lengths are traced, so 0..K
            # accepts share this executable. Lane 0's sampled draw uses
            # the row's ordinary fold schedule — a zero-draft row (and
            # every sampled row) commits exactly the plain decode_step
            # token.
            return self._spec_verify(
                self._forward(params, toks, cache, pos, lora),
                toks, folds, greedy, t, k, p, keydata, n_draft,
            )

        return {
            "prefill": prefill,
            "decode_step": decode_step,
            "decode_spec_step": decode_spec_step,
        }

    @staticmethod
    def _spec_verify(forward_out, toks, folds, greedy, t, k, p,
                     keydata, n_draft):
        """Shared verification tail of both engines' spec bodies (the
        dense/paged programs differ only in how the forward is wired):
        sample lane 0 with the row's key/fold (bit-matching the plain
        step), take the model's own greedy chain over the window, and
        compute the traced accept lengths. Returns
        (out [B, K+1], n_acc [B], bad [B], cache) — the host commits
        ``out[b, :n_acc[b]+1]``, clipped by EOS/budget."""
        logits, cache = forward_out  # [B, K+1, V]
        keys = jax.vmap(jax.random.fold_in)(
            jax.random.wrap_key_data(keydata), folds
        )
        tok0 = decode.sample_token_rows(
            logits[:, 0], greedy, t, keys, k, p
        )
        ver = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
        n_acc = decode.speculative_accept(
            toks[:, 1:], ver[:, :-1], n_draft
        )
        out = jnp.concatenate([tok0[:, None], ver[:, 1:]], axis=1)
        # NaN anywhere in the window flags the row: any lane's logits
        # could decide a committed token (one reduction, no collectives
        # — the pinned budgets are untouched, like every sentinel).
        return out, n_acc, decode.nonfinite_rows(logits), cache

    def _lora_dispatch_args(self, tenant_slots) -> tuple:
        """The two trailing LoRA operands for one dispatch — the
        (version-memoized) stacked adapter tree and the per-row tenant
        slots — or () when no registry is attached (the signatures then
        stay the pre-LoRA ones). Free/garbage rows ride slot 0, the
        exact-zero adapter."""
        if self.adapters is None:
            return ()
        return (
            self.adapters.device_tree(),
            jnp.asarray(tenant_slots, jnp.int32),
        )

    def _lora_in_specs(self) -> tuple:
        """shard_map in_specs for the two LoRA operands under TP (empty
        without a registry): the factor tree shards per
        ``AdapterRegistry.partition_specs`` — column-parallel B factors
        with their base weight's output axis, row-parallel A factors on
        the contracting dim — and the tenant-slot vector replicates."""
        if self.adapters is None:
            return ()
        from jax.sharding import PartitionSpec as P

        return (self.adapters.partition_specs(), P())

    def _check_program_kind(self, kind: str) -> None:
        if kind not in _BATCHED_PROGRAM_KINDS:
            raise KeyError(f"unknown batched program kind {kind!r}")
        if kind == "decode_spec_step" and not self.speculative_k:
            raise KeyError(
                "decode_spec_step exists only on engines built with "
                "speculative_k > 0 (this engine decodes one token per "
                "row per tick)"
            )
        if kind == "decode_step" and self.speculative_k:
            # Symmetric gate: a spec engine routes EVERY decode tick
            # through decode_spec_step, so silently building the plain
            # step here would cache an executable the engine never
            # dispatches — and inflate compile_count() under the pinned
            # zero-steady-compile assertions.
            raise KeyError(
                "this engine was built with speculative_k="
                f"{self.speculative_k}: every decode tick dispatches "
                "decode_spec_step — request that kind instead"
            )

    def _program_kinds(self) -> tuple[str, ...]:
        """The program kinds THIS engine actually dispatches: a spec
        engine's every decode tick goes through decode_spec_step (rows
        without drafts ride zero-draft lanes), so the plain decode_step
        is never built there — and vice versa."""
        return (
            "prefill",
            "decode_spec_step" if self.speculative_k else "decode_step",
        )

    def program(self, kind: str):
        """The jitted program for ``kind`` — public for the audit
        registry (analysis/registry.py) and tests, like
        ``DecodeEngine.program``."""
        self._check_program_kind(kind)
        prog = self._programs.get(kind)
        if prog is not None:
            return prog
        body = self._bodies()[kind]
        donate = (self.CACHE_ARGNUM[kind],)
        if self.mode == "plain":
            prog = jax.jit(body, donate_argnums=donate)
        else:  # tp
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_tpu.utils.compat import shard_map

            cache_spec = {
                "k": P(None, None, None, "tensor", None),
                "v": P(None, None, None, "tensor", None),
            }
            specs = {
                "prefill": (
                    self._p_specs, P(), P(), P(), cache_spec,
                    P(), P(), P(), P(), P(),
                ),
                "decode_step": (
                    self._p_specs, P(), cache_spec, P(), P(),
                    P(), P(), P(), P(), P(),
                ),
                # decode_step + the [B] n_draft operand; outputs grow
                # the replicated [B] accept lengths.
                "decode_spec_step": (
                    self._p_specs, P(), cache_spec, P(), P(),
                    P(), P(), P(), P(), P(), P(),
                ),
            }[kind] + self._lora_in_specs()
            out_specs = (
                (P(), P(), P(), cache_spec)
                if kind == "decode_spec_step"
                else (P(), P(), cache_spec)
            )
            smapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=specs,
                out_specs=out_specs,
                check_vma=True,
            )
            prog = jax.jit(smapped, donate_argnums=donate)
        self._programs[kind] = prog
        return prog

    def _place_params(self, params):
        if (self.mode == "plain" and self.weight_quant == "none"
                and self.device is None):
            return params
        if self._placed is None or self._placed[0] is not params:
            prepared = (
                quantize_decode_params(params)
                if self.weight_quant != "none"
                else params
            )
            if self.mode != "plain":
                prepared = jax.device_put(prepared, self._param_shardings)
            elif self.device is not None:
                prepared = jax.device_put(prepared, self.device)
            self._placed = (params, prepared)
        return self._placed[1]

    def device_ids(self) -> list[int]:
        """Process-local device ids this engine's programs run on —
        ``stats()``'s placement figure (see DecodeEngine.device_ids)."""
        if self.mode == "plain":
            d = self.device if self.device is not None else jax.devices()[0]
            return [d.id]
        return [d.id for d in self._mesh.devices.flat]

    # -- request API -------------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        key: jax.Array | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
        eos_id: int | None = None,
        timeout_s: float | None = None,
        params=None,
        block_timeout_s: float | None = None,
        priority: str = STANDARD,
        session: int | None = None,
        tenant=None,
    ) -> int:
        """Queue one single-sequence request ([Tp] or [1, Tp] int ids);
        returns its request id. The request is admitted into a free slot
        by a later ``step``; its terminal ``RequestResult`` lands in
        ``self.results[rid]`` — collect it with ``pop_result(rid)``
        (long-lived engines leak host memory otherwise).

        ``timeout_s``: per-request deadline on the ENGINE clock; a
        request still queued or mid-decode when it passes retires
        EXPIRED with its clean partial output. Backpressure: with no
        ``queue_limit`` the queue itself is the backpressure (submissions
        beyond the slot count wait their FIFO turn); with one, the
        ``reject`` policy raises ``AdmissionQueueFull`` loudly, and the
        ``block`` policy drives the scheduler (``params`` required) until
        space frees or ``block_timeout_s`` passes, then raises.

        Workload scenarios (all host-side — traced programs never see
        them): ``priority`` is the SLO tier (serving/scheduler.py —
        'interactive' admits ahead of the queue, deadline-first within
        the tier; 'standard' is exactly the pre-tier FIFO). On the
        DENSE engine tiers only reorder admission; the paged engine
        additionally lets interactive preempt lower tiers, gates
        'batch' admission on pool headroom, and preempts batch first.
        ``session`` is a live session id from the
        paged engine's ``open_session`` — the prompt must resubmit the
        conversation-so-far and pays ~one chunk of prefill via the
        pinned prefix cache. ``tenant`` picks a registered LoRA adapter
        (engine built with ``adapters=``); None rides the shared zero
        adapter bit-equal to the adapter-less engine."""
        prompt = np.asarray(prompt)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1:
            raise ValueError(
                f"BatchedDecodeEngine serves one sequence per request "
                f"(one slot row); got prompt shape {prompt.shape}"
            )
        tp = prompt.shape[0]
        decode._check_sample_args(
            prompt, max_new_tokens, temperature, key, max_len=self.max_len
        )
        if timeout_s is not None and timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        tier = check_priority(priority)
        tenant_slot = 0
        if tenant is not None:
            if self.adapters is None:
                raise ValueError(
                    f"tenant={tenant!r} needs an engine built with "
                    "adapters=AdapterRegistry(...) — this engine has no "
                    "adapter registry attached"
                )
            tenant_slot = self.adapters.slot(tenant)
        prompt = prompt.astype(np.int32)
        # Validates BEFORE the rid is assigned (a rejected turn must not
        # burn an id) but marks the turn in flight only after.
        resub_len = self._session_checkin(session, prompt)
        self._admission_backpressure(params, block_timeout_s)
        rid = self._next_rid
        self._next_rid += 1
        bucket = self.buckets.bucket_for(tp)
        t, k, p = decode.sampling_scalars(
            temperature, top_k, top_p, self.cfg.vocab_size
        )
        keydata = (
            np.asarray(jax.random.key_data(key))
            if key is not None
            else np.zeros((self._key_words,), np.uint32)
        )
        deadline = (
            None if timeout_s is None else self._clock() + timeout_s
        )
        self._queue.append(_Pending(
            rid=rid, prompt=prompt, bucket=bucket,
            max_new=int(max_new_tokens), eos_id=eos_id,
            greedy=not temperature > 0.0,
            t=float(t), k=int(k), p=float(p), keydata=keydata,
            prefill_keydata=keydata, deadline=deadline,
            tier=tier, session=session, resub_len=resub_len,
            tenant_slot=tenant_slot,
            stamps=_Stamps(submit=self._clock()),
        ))
        self._session_begin(session, rid)
        log_event(
            "submit", rid=rid, t=round(self._clock(), 6), prompt_len=tp,
            max_new=int(max_new_tokens),
            deadline=None if deadline is None else round(deadline, 6),
            priority=priority if tier != TIER_RANK[STANDARD] else None,
            session=session,
            tenant=str(tenant) if tenant is not None else None,
        )
        return rid

    def _session_checkin(self, session, prompt) -> int:
        """Hook: validate a session turn and return its resubmitted-
        transcript length. Sessions ride the paged engine's prefix cache
        — the dense engines reject them loudly."""
        if session is not None:
            raise ValueError(
                "multi-turn sessions need the chunk-chained prefix "
                "cache and page pinning — open them on a "
                "PagedBatchedDecodeEngine (serving/session.py), not "
                f"{type(self).__name__}"
            )
        return 0

    def _session_begin(self, session, rid) -> None:
        """Hook: mark a validated session turn in flight (paged only)."""

    def _admission_backpressure(self, params, block_timeout_s) -> None:
        if self.queue_limit is None or len(self._queue) < self.queue_limit:
            return
        if self.backpressure == "reject":
            raise AdmissionQueueFull(
                f"admission queue full: {len(self._queue)} queued >= "
                f"queue_limit {self.queue_limit} (policy 'reject') — "
                "shed load upstream or retry after draining"
            )
        # block: drive the scheduler until space frees or timeout.
        if params is None:
            raise ValueError(
                "backpressure policy 'block' drives the scheduler from "
                "submit and therefore needs params=... (or use the "
                "'reject' policy)"
            )
        deadline = (
            None
            if block_timeout_s is None
            else self._clock() + block_timeout_s
        )
        while len(self._queue) >= self.queue_limit:
            if deadline is not None and self._clock() >= deadline:
                raise AdmissionQueueFull(
                    f"admission queue still full ({len(self._queue)} >= "
                    f"queue_limit {self.queue_limit}) after blocking "
                    f"{block_timeout_s}s — the engine is not draining "
                    "fast enough for the offered load"
                )
            self.step(params)

    def has_work(self) -> bool:
        return bool(self._queue) or any(
            s is not None for s in self._slots
        )

    def queued_rids(self) -> list[int]:
        return [q.rid for q in self._queue]

    def active_rids(self) -> list[int]:
        return [s.rid for s in self._slots if s is not None]

    def abort(self, rid: int) -> bool:
        """Cancel one request mid-flight. Pure host bookkeeping: a
        queued entry is removed, an ACTIVE slot row is freed (its K/V
        stays in place, dirty — the traced shapes and the compiled
        programs are untouched, so an abort can never recompile and
        neighbours decode on unperturbed). The request retires ABORTED
        with its clean partial output. Returns True on transition, False
        if the request already reached a terminal state; unknown rids
        raise KeyError."""
        for q in self._queue:
            if q.rid == rid:
                self._queue.remove(q)
                self._finish_pending(q, ABORTED, "abort() while queued")
                return True
        for i, s in enumerate(self._slots):
            if s is not None and s.rid == rid:
                self._slots[i] = None
                self._on_slot_freed(s)
                self._finish_slot(s, ABORTED, "abort() mid-decode")
                return True
        if rid in self.results:
            return False
        raise KeyError(
            f"unknown rid {rid}: never submitted, or already delivered "
            "via pop_result"
        )

    def step(self, params) -> list[int]:
        """One scheduler tick: expire overdue requests, admit queued
        requests into free slots (prefill), then advance every active
        row one token (one batched decode dispatch). Returns the rids
        that reached a terminal state this tick.

        A failed/dropped dispatch is RECOVERED here, not surfaced: every
        in-flight row converts to a resume entry (re-prefilled from its
        tokens-so-far on a later tick), bounded by per-request
        ``request_retries``; only when ``dispatch_retries`` CONSECUTIVE
        dispatches fail does step raise ``DispatchFailure`` — with the
        engine state still consistent (everything requeued)."""
        with self.timers.span("engine.tick"):
            self._ticks += 1
            if self._injector is not None:
                self._injector.on_tick(self._ticks)
            params = self._place_params(params)
            finished: list[int] = []
            with self.timers.span("engine.expire"):
                self._expire(finished)
            self._admit(params, finished)
            if any(s is not None for s in self._slots):
                self._decode_tick(params, finished)
            return finished

    def run(
        self, params, requests=None, *,
        max_ticks: int | None = None,
        timeout_s: float | None = None,
    ) -> dict[int, RequestResult]:
        """Submit ``requests`` (iterable of ``submit`` kwarg dicts), then
        drive ``step`` until idle. Returns {rid: RequestResult} for
        everything that reached a terminal state during the drive
        (including previously queued work).

        ``max_ticks`` / ``timeout_s`` (engine clock) bound the drive: a
        hung or permanently-faulting stream terminates with the partial
        results collected so far (remaining work stays queued/active in
        the engine) instead of looping forever."""
        before = set(self.results)
        for req in requests or ():
            self.submit(**req)
        deadline = (
            None if timeout_s is None else self._clock() + timeout_s
        )
        ticks = 0
        while self.has_work():
            if max_ticks is not None and ticks >= max_ticks:
                log_event(
                    "run_guard", reason="max_ticks", ticks=ticks,
                    queued=len(self._queue),
                    active=len(self.active_rids()),
                )
                break
            if deadline is not None and self._clock() >= deadline:
                log_event(
                    "run_guard", reason="timeout", ticks=ticks,
                    queued=len(self._queue),
                    active=len(self.active_rids()),
                )
                break
            self.step(params)
            ticks += 1
        return {
            rid: out for rid, out in self.results.items()
            if rid not in before
        }

    def pop_result(self, rid: int) -> RequestResult:
        """Deliver and RELEASE one request's terminal ``RequestResult``
        (state DONE/FAILED/ABORTED/EXPIRED + tokens + reason), dropping
        the engine's reference. A long-lived engine retains every
        retired request's result in ``results`` until delivered —
        serving loops must pop (or ``del``) what they consume, or host
        memory grows per request forever. KeyError for unknown or
        not-yet-terminal rids."""
        return self.results.pop(rid)

    def warmup(self, params) -> int:
        """Compile every (bucket x prefill-group) shape plus the decode
        program with dummy dispatches (idle engines only — warmup writes
        garbage rows), so a serving loop's steady state starts
        compile-free. Covers the fault-resume max_len bucket too, so
        recovery re-prefills never compile mid-incident. Returns
        compile_count()."""
        if self.has_work():
            raise RuntimeError("warmup requires an idle engine")
        if not self._prefill_buckets:
            raise ValueError(
                "warmup needs a finite BucketSpec (exact-length mode "
                "compiles per observed prompt length)"
            )
        params = self._place_params(params)
        for bucket in self._prefill_buckets:
            for g in self._groups:
                args = self.example_args(
                    "prefill", params, bucket=bucket, group=g,
                    cache=self._take_cache(),
                )
                _, _, cache = self.program("prefill")(*args)
                self._cache = cache
        self._rewarm_first_prefill(params)
        step_kind = self._program_kinds()[-1]
        args = self.example_args(
            step_kind, params, cache=self._take_cache()
        )
        *_, cache = self.program(step_kind)(*args)
        self._cache = cache
        return self.compile_count()

    def _rewarm_first_prefill(self, params) -> None:
        """Close a meshed-warmup hole: the warmup loop's FIRST dispatch
        keyed its executable on the freshly ``device_put`` cache's
        sharding, but every steady-state dispatch presents the
        donated-OUTPUT sharding instead — which can hash differently,
        so the first shape recompiled once mid-traffic (observed on TP;
        regression-pinned by the zero-steady-compile assertion of
        tests/test_serving_spec.py::test_spec_tp_matches_plain_tp).
        Re-dispatching that one
        shape with the laundered cache keys the warm set exactly as
        serving will hit it."""
        if self.mode == "plain":
            return
        args = self.example_args(
            "prefill", params,
            bucket=(
                self._prefill_buckets[0] if self._prefill_buckets
                else None
            ),
            group=self._groups[0], cache=self._take_cache(),
        )
        _, _, cache = self.program("prefill")(*args)
        self._cache = cache

    # -- fault injection / crash recovery ------------------------------------

    def set_fault_injector(self, injector) -> None:
        """Install a serving/chaos.FaultInjector (or None to remove):
        host-side hooks consulted around every dispatch and at every
        tick — nothing traced ever sees it, so injection cannot change
        compiled programs or their budgets."""
        self._injector = injector
        if injector is not None:
            # Seeded nan_row faults pick their target among the active
            # rows, so the injector needs the engine back-reference
            # whichever way it was attached (here or injector.install).
            injector._engine = self

    def snapshot(self) -> EngineSnapshot:
        """Capture the engine's full host-side request state (between
        ``step`` calls): queued entries, every in-flight row as a resume
        entry carrying its tokens-so-far and pre-folded PRNG schedule,
        the rid counter, and undelivered results. Device state (the KV
        cache) is deliberately NOT captured — it is reconstructible from
        the prefixes, which is exactly what ``restore`` + the admission
        path do."""
        inflight = [
            self._pending_from_slot(s, bump=False)
            for s in self._slots if s is not None
        ]
        inflight.sort(key=lambda q: q.rid)
        queued = [
            dataclasses.replace(q, gen=list(q.gen)) for q in self._queue
        ]
        log_event(
            "snapshot", t=round(self._clock(), 6),
            inflight=len(inflight), queued=len(queued),
        )
        return EngineSnapshot(
            pending=inflight + queued,
            next_rid=self._next_rid,
            results=dict(self.results),
            stats=dict(self.counters),
        )

    def restore(self, snap: EngineSnapshot) -> None:
        """Load a ``snapshot`` into this (fresh, idle) engine — the
        crash-recovery path: after a device loss kills the old engine
        (and its donated cache), a rebuilt engine restores and its next
        ``step``s re-prefill every in-flight request from its
        tokens-so-far, continuing token-identically to an uninterrupted
        run (the per-request fold schedule rides in the entries).
        Buckets are recomputed against THIS engine's spec, so the
        snapshot survives a bucket-config change on rebuild."""
        if self.has_work() or self.results:
            raise RuntimeError(
                "restore requires a fresh idle engine (no queued/active "
                "work, no undelivered results)"
            )
        self._next_rid = snap.next_rid
        self.results.update(snap.results)
        for q in snap.pending:
            prefix = len(q.prompt) + len(q.gen)
            if prefix + (q.max_new - len(q.gen)) > self.max_len:
                raise ValueError(
                    f"snapshot entry rid {q.rid} needs "
                    f"{prefix + q.max_new - len(q.gen)} cache positions "
                    f"but this engine's max_len is {self.max_len}"
                )
            bucket = (
                self._resume_bucket(prefix)
                if q.gen
                else self.buckets.bucket_for(len(q.prompt))
            )
            # Session linkage is ENGINE-LOCAL and the restored engine's
            # tracker is fresh (sid 0 will be handed out again): keeping
            # the old sid would let a new session collide with it and
            # corrupt its transcript. The turn completes as a plain
            # request; its client re-opens (transcript-carrying
            # resubmission makes that lossless).
            self._queue.append(dataclasses.replace(
                q, bucket=bucket, gen=list(q.gen), session=None,
            ))
        log_event(
            "restore", t=round(self._clock(), 6),
            pending=len(snap.pending), next_rid=snap.next_rid,
        )

    def adopt(self, entries) -> dict[int, int]:
        """Take over queued/resume entries from ANOTHER engine — the
        router's failover path: when a replica dies, its host-side
        entries (in-flight rows already converted to resume entries
        carrying tokens-so-far + the pre-folded PRNG schedule) are
        adopted by survivors and continue BIT-IDENTICALLY, because the
        continuation depends only on the entry and the (shared) params,
        never on which engine runs it. Unlike ``restore`` this works on
        a BUSY engine: each entry is assigned THIS engine's next rid
        (the donor's rids would collide) and appended in the order
        given — adopted work queues behind traffic already admitted
        here, which is the deterministic choice a router can reason
        about. Returns {donor_rid: adopted_rid}; the caller (the
        router) owns the mapping."""
        entries = list(entries)
        # Validate EVERYTHING before touching the queue: a mixed batch
        # with one oversized entry must not half-adopt (the caller would
        # have no mapping for the entries already enqueued).
        for q in entries:
            if len(q.prompt) + q.max_new > self.max_len:
                raise ValueError(
                    f"adopted entry rid {q.rid} needs "
                    f"{len(q.prompt) + q.max_new} cache positions "
                    f"but this engine's max_len is {self.max_len}"
                )
        mapping: dict[int, int] = {}
        for q in entries:
            prefix = len(q.prompt) + len(q.gen)
            rid = self._next_rid
            self._next_rid += 1
            bucket = (
                self._resume_bucket(prefix)
                if q.gen
                else self.buckets.bucket_for(len(q.prompt))
            )
            # Donor session ids mean nothing here (and could collide
            # with a LIVE local session, corrupting its transcript):
            # adopted turns finish as plain requests; the router's
            # stickiness layer re-opens the session on the survivor.
            self._queue.append(dataclasses.replace(
                q, rid=rid, bucket=bucket, gen=list(q.gen), session=None,
            ))
            mapping[q.rid] = rid
        return mapping

    def peek_tokens(self, rid: int, since: int = 0) -> np.ndarray | None:
        """Tokens-so-far for a live OR terminal request (prompt + every
        clean token generated to date), from index ``since`` on — the
        host-side progress read the SSE streaming front door makes every
        tick (past the prompt it copies the new tokens only). None for
        unknown rids; never touches device state."""
        for s in self._slots:
            if s is not None and s.rid == rid:
                return self._partial_tokens(s.prompt, s.generated, since)
        for q in self._queue:
            if q.rid == rid:
                return self._partial_tokens(q.prompt, q.gen, since)
        res = self.results.get(rid)
        return None if res is None else np.asarray(res.tokens)[since:]

    # -- scheduler internals -----------------------------------------------

    def _resume_bucket(self, length: int) -> int:
        """Smallest warmed prefill shape covering a resume prefix (the
        user buckets extended by max_len; exact length in exact mode)."""
        for b in self._prefill_buckets:
            if b >= length:
                return b
        return length

    def _prefill_keydata(self, req_keydata, g: int, greedy: bool):
        """The key the admission prefill must draw with so a resumed
        request's next token bit-matches the undisturbed run: token g of
        a request is sampled with fold_in(base_key, g - 1) (g = 0: the
        unfolded base key). Folded HOST-side — a rare, tiny dispatch —
        so the compiled prefill keeps its one uniform signature."""
        if greedy or g == 0:
            return req_keydata
        key = jax.random.wrap_key_data(jnp.asarray(req_keydata))
        return np.asarray(
            jax.random.key_data(jax.random.fold_in(key, g - 1))
        )

    def _pending_from_slot(
        self, s: _Slot, *, bump: bool, nan_retried: bool | None = None
    ) -> _Pending:
        """Convert an in-flight row to a resume entry: the clean tokens
        generated so far become the prefill prefix; ``bump`` charges one
        fault-resume against the request's retry budget."""
        g = len(s.generated)
        prefix = len(s.prompt) + g
        return _Pending(
            rid=s.rid, prompt=s.prompt, bucket=self._resume_bucket(prefix),
            max_new=s.max_new, eos_id=s.eos_id, greedy=s.greedy,
            t=s.t, k=s.k, p=s.p, keydata=s.keydata,
            prefill_keydata=self._prefill_keydata(s.keydata, g, s.greedy),
            deadline=s.deadline, gen=list(s.generated),
            retries=s.retries + (1 if bump else 0),
            nan_retried=s.nan_retried if nan_retried is None else nan_retried,
            tier=s.tier, session=s.session, resub_len=s.resub_len,
            tenant_slot=s.tenant_slot, stamps=s.stamps,
        )

    def _partial_tokens(self, prompt, gen, since: int = 0) -> np.ndarray:
        if since >= len(prompt):
            return np.asarray(gen[since - len(prompt):], np.int32)
        return np.concatenate(
            [np.asarray(prompt, np.int32), np.asarray(gen, np.int32)]
        )[since:]

    def _stamp_row(self, st: _Stamps) -> None:
        """The request holds a row; the first time ends its queue wait."""
        if st.row is None:
            st.row = self._clock()
            self.timers.add("queue_wait", st.row - st.submit)

    def _stamp_first_token(self, st: _Stamps) -> None:
        if st.first_token is None:
            st.first_token = self._clock()

    def _finish(self, rid, state, tokens, reason, stamps: _Stamps,
                finished: list[int] | None = None) -> None:
        self.results[rid] = RequestResult(
            rid=rid, state=state, tokens=tokens, reason=reason
        )
        self.counters[state.lower()] += 1
        if finished is not None:
            finished.append(rid)
        now = self._clock()
        prefill_s = decode_s = None
        if stamps.first_token is not None:
            prefill_s = stamps.first_token - stamps.row
            decode_s = now - stamps.first_token
            self.timers.add("request.prefill", prefill_s)
            self.timers.add("request.decode", decode_s)
        log_event(
            "retire", rid=rid, state=state, t=round(now, 6),
            n_tokens=len(tokens), reason=reason or None,
            queue_wait_s=round(
                (now if stamps.row is None else stamps.row)
                - stamps.submit, 6),
            prefill_s=None if prefill_s is None else round(prefill_s, 6),
            decode_s=None if decode_s is None else round(decode_s, 6),
        )

    def _finish_pending(self, q: _Pending, state, reason,
                        finished=None) -> None:
        self._finish(
            q.rid, state, self._partial_tokens(q.prompt, q.gen), reason,
            q.stamps, finished,
        )

    def _finish_slot(self, s: _Slot, state, reason, finished=None) -> None:
        self._finish(
            s.rid, state, self._partial_tokens(s.prompt, s.generated),
            reason, s.stamps, finished,
        )

    def _requeue(self, pendings) -> None:
        """Merge resume/rewound entries back into the admission queue in
        ascending-rid order — rids are assigned at submit, so rid order
        IS global FIFO order: a resumed old request re-admits before
        younger traffic, keeping scheduling deterministic under faults."""
        if not pendings:
            return
        items = sorted(
            list(self._queue) + list(pendings), key=lambda q: q.rid
        )
        self._queue = collections.deque(items)

    def _expire(self, finished: list[int]) -> None:
        now = self._clock()
        overdue = [
            q for q in self._queue
            if q.deadline is not None and now >= q.deadline
        ]
        for q in overdue:
            self._queue.remove(q)
            self._finish_pending(
                q, EXPIRED,
                f"deadline passed at t={now:.3f} while queued", finished,
            )
        for i, s in enumerate(self._slots):
            if s is not None and s.deadline is not None and now >= s.deadline:
                self._slots[i] = None
                self._on_slot_freed(s)
                self._finish_slot(
                    s, EXPIRED,
                    f"deadline passed at t={now:.3f} mid-decode", finished,
                )

    def _queue_key(self, q: _Pending):
        """Admission order: tier rank, then (INTERACTIVE only) earliest
        deadline, then rid — scheduler.queue_key. An all-STANDARD queue
        sorts exactly by rid, i.e. the pre-tier FIFO (regression-pinned
        in tests/test_serving_scenarios.py)."""
        return queue_key(q.tier, q.deadline, q.rid)

    def _admit(self, params, finished: list[int]) -> None:
        with self.timers.span("engine.admit"):
            free = [i for i, s in enumerate(self._slots) if s is None]
            n = min(len(free), len(self._queue))
            if not n:
                return
            admitted = sorted(self._queue, key=self._queue_key)[:n]
            for q in admitted:
                self._queue.remove(q)
            # Priority-then-FIFO admission (interactive bypasses the
            # queue head; an all-standard stream keeps the exact pre-tier
            # order); arrivals sharing a bucket prefill as one batched
            # dispatch (group padded to the next allowed size).
            by_bucket: dict[int, list[tuple[_Pending, int]]] = {}
            for req in admitted:
                by_bucket.setdefault(req.bucket, []).append(
                    (req, free.pop(0))
                )
                self._stamp_row(req.stamps)
            groups = list(by_bucket.items())
        for gi, (bucket, group) in enumerate(groups):
            if not self._prefill_group(params, bucket, group, finished):
                # Dispatch failed: recovery requeued this group and every
                # in-flight row; rewind the not-yet-dispatched groups
                # untouched (no retry charge — they were never at risk)
                # and stop admitting this tick.
                rest = [
                    pend for _, g in groups[gi + 1:] for pend, _ in g
                ]
                self._requeue(rest)
                return

    def _prefill_group(self, params, bucket, group, finished) -> bool:
        """One bucket's admission dispatch. Returns False when the
        dispatch failed (recovery already ran)."""
        with self.timers.span("engine.build.prefill"):
            n = len(group)
            npad = next(g for g in self._groups if g >= n)
            # Pad the group by DUPLICATING entry 0 (same row index, same
            # data): the overlapping scatter writes are bit-identical,
            # and the duplicate's sampled token is discarded.
            idx = list(range(n)) + [0] * (npad - n)
            prompts = np.zeros((npad, bucket), np.int32)
            plens = np.zeros((npad,), np.int32)
            rows = np.zeros((npad,), np.int32)
            greedy = np.zeros((npad,), np.bool_)
            t = np.ones((npad,), np.float32)
            k = np.full((npad,), self.cfg.vocab_size, np.int32)
            p = np.full((npad,), 2.0, np.float32)
            keydata = np.zeros((npad, self._key_words), np.uint32)
            tenants = np.zeros((npad,), np.int32)
            for j, i in enumerate(idx):
                req, row = group[i]
                prefix = self._partial_tokens(req.prompt, req.gen)
                prompts[j, : prefix.shape[0]] = prefix
                plens[j] = prefix.shape[0]
                rows[j] = row
                greedy[j] = req.greedy
                t[j], k[j], p[j] = req.t, req.k, req.p
                keydata[j] = req.prefill_keydata
                tenants[j] = req.tenant_slot
            with self.timers.span("engine.put.prefill"):
                args = (
                    jnp.asarray(prompts), jnp.asarray(plens),
                    jnp.asarray(rows), None, jnp.asarray(greedy),
                    jnp.asarray(t), jnp.asarray(k), jnp.asarray(p),
                    jnp.asarray(keydata), *self._lora_dispatch_args(tenants),
                )
        res = self._dispatch(
            "prefill", params, [req for req, _ in group], finished, *args
        )
        if res is None:
            return False
        toks, bad = res
        with self.timers.span("engine.settle.prefill"):
            for i, (req, row) in enumerate(group):
                if bad[i]:
                    self._quarantine_pending(req, finished)
                    continue
                self._slots[row] = _Slot(
                    rid=req.rid, prompt=req.prompt, max_new=req.max_new,
                    eos_id=req.eos_id, pos=int(plens[i]),
                    fold=len(req.gen),
                    generated=list(req.gen) + [int(toks[i])],
                    greedy=req.greedy, t=req.t, k=req.k, p=req.p,
                    keydata=req.keydata, deadline=req.deadline,
                    retries=req.retries, nan_retried=req.nan_retried,
                    tier=req.tier, session=req.session,
                    resub_len=req.resub_len, tenant_slot=req.tenant_slot,
                    stamps=req.stamps,
                )
                self._stamp_first_token(req.stamps)
                log_event(
                    "admit", rid=req.rid, row=row, bucket=bucket,
                    resume_prefix=len(req.gen) or None,
                    t=round(self._clock(), 6),
                )
                self._maybe_retire(row, finished)
        return True

    # -- speculation (host side) -------------------------------------------

    def _draft_tokens(self, s: _Slot) -> np.ndarray:
        """Up to ``speculative_k`` draft tokens for one active row —
        prompt-lookup over the row's tokens-so-far (or the engine's
        ``draft_hook``), capped so every COMMITTABLE token's position
        stays inside the row's budget and the cache extent. Sampled
        rows draft nothing (exact sampled speculation needs rejection-
        sampling corrections — out of scope, models/speculative.py);
        they still ride the same program with zero-draft lanes."""
        if not s.greedy:
            return _EMPTY_DRAFT
        cap = min(
            self.speculative_k,
            s.max_new - len(s.generated) - 1,
            self.max_len - s.pos - 1,
        )
        if cap <= 0:
            return _EMPTY_DRAFT
        hist = self._partial_tokens(s.prompt, s.generated)
        if self._draft_hook is not None:
            d = np.asarray(
                self._draft_hook(hist, cap), np.int32
            ).reshape(-1)[:cap]
            # Hook output is advisory: clip to the vocab so a buggy
            # hook can cost speed (rejected drafts) but never an OOB
            # embedding lookup.
            return np.clip(d, 0, self.cfg.vocab_size - 1)
        from pytorch_distributed_tpu.models.speculative import (
            prompt_lookup_draft,
        )

        return prompt_lookup_draft(hist, cap, ngram=self.spec_ngram)

    def _commit_spec(self, row: int, s: _Slot, out_row: np.ndarray,
                     n_acc: int, n_draft: int, finished) -> None:
        """Commit one row's verified window: accepted drafts plus the
        model's bonus/correction token, clipped at EOS and the row's
        budget. Rejected drafts are rolled back by simply not advancing
        ``pos`` past the commit — their K/V garbage sits beyond the
        row's depth, masked by the pos discipline and overwritten by
        later writes (on the paged engine it is confined to the row's
        private tail page)."""
        committed = 0
        for tok in out_row[: n_acc + 1]:
            s.generated.append(int(tok))
            s.pos += 1
            s.fold += 1
            committed += 1
            if len(s.generated) >= s.max_new or (
                s.eos_id is not None and int(tok) == s.eos_id
            ):
                break  # EOS inside the window: later lanes discarded
        self.counters["drafted_tokens"] += n_draft
        self.counters["accepted_tokens"] += committed - 1
        self.counters["spec_commits"] += 1
        if n_draft:
            log_event(
                "draft_accept", rid=s.rid, drafted=n_draft,
                accepted=committed - 1, t=round(self._clock(), 6),
            )
        self._maybe_retire(row, finished)

    def _decode_tick_spec(self, params, finished: list[int]) -> None:
        """The speculative twin of ``_decode_tick``: every active row's
        lane-0 token plus its host drafts go through ONE k+1-wide
        verify forward; per-row accept lengths come back traced."""
        with self.timers.span("engine.build.decode"):
            b, width = self.slots, self.speculative_k + 1
            toks = np.zeros((b, width), np.int32)
            n_draft = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            folds = np.zeros((b,), np.int32)
            greedy = np.ones((b,), np.bool_)
            t = np.ones((b,), np.float32)
            k = np.full((b,), self.cfg.vocab_size, np.int32)
            p = np.full((b,), 2.0, np.float32)
            keydata = np.zeros((b, self._key_words), np.uint32)
            tenants = np.zeros((b,), np.int32)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue  # free rows verify garbage the host discards
                drafts = self._draft_tokens(s)
                toks[i, 0] = s.generated[-1]
                toks[i, 1 : 1 + len(drafts)] = drafts
                n_draft[i] = len(drafts)
                pos[i] = s.pos
                folds[i] = s.fold
                greedy[i] = s.greedy
                t[i], k[i], p[i] = s.t, s.k, s.p
                keydata[i] = s.keydata
                tenants[i] = s.tenant_slot
            with self.timers.span("engine.put.decode_spec_step"):
                args = (
                    jnp.asarray(toks), None, jnp.asarray(pos),
                    jnp.asarray(folds), jnp.asarray(greedy), jnp.asarray(t),
                    jnp.asarray(k), jnp.asarray(p), jnp.asarray(keydata),
                    jnp.asarray(n_draft), *self._lora_dispatch_args(tenants),
                )
        res = self._dispatch(
            "decode_spec_step", params, None, finished, *args
        )
        if res is None:
            return
        out, n_acc, bad = res
        with self.timers.span("engine.settle.decode"):
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                if bad[i]:
                    self._slots[i] = None
                    self._on_slot_freed(s)
                    self._quarantine_slot(s, i, finished)
                    continue
                self._commit_spec(
                    i, s, out[i], int(n_acc[i]), int(n_draft[i]), finished
                )

    def _decode_tick(self, params, finished: list[int]) -> None:
        if self.speculative_k:
            return self._decode_tick_spec(params, finished)
        with self.timers.span("engine.build.decode"):
            b = self.slots
            toks = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            folds = np.zeros((b,), np.int32)
            greedy = np.ones((b,), np.bool_)
            t = np.ones((b,), np.float32)
            k = np.full((b,), self.cfg.vocab_size, np.int32)
            p = np.full((b,), 2.0, np.float32)
            keydata = np.zeros((b, self._key_words), np.uint32)
            tenants = np.zeros((b,), np.int32)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue  # free rows decode garbage the host discards
                toks[i] = s.generated[-1]
                pos[i] = s.pos
                folds[i] = s.fold
                greedy[i] = s.greedy
                t[i], k[i], p[i] = s.t, s.k, s.p
                keydata[i] = s.keydata
                tenants[i] = s.tenant_slot
            with self.timers.span("engine.put.decode_step"):
                args = (
                    jnp.asarray(toks), None, jnp.asarray(pos),
                    jnp.asarray(folds), jnp.asarray(greedy), jnp.asarray(t),
                    jnp.asarray(k), jnp.asarray(p), jnp.asarray(keydata),
                    *self._lora_dispatch_args(tenants),
                )
        res = self._dispatch("decode_step", params, None, finished, *args)
        if res is None:
            return
        out, bad = res
        with self.timers.span("engine.settle.decode"):
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                if bad[i]:
                    self._slots[i] = None
                    self._on_slot_freed(s)
                    self._quarantine_slot(s, i, finished)
                    continue
                s.generated.append(int(out[i]))
                s.pos += 1
                s.fold += 1
                self._maybe_retire(i, finished)

    def _quarantine_pending(self, req: _Pending, finished) -> None:
        """Non-finite logits in an admission prefill: the garbage token
        is discarded and the request retried once over a freshly
        re-prefilled row, then FAILED."""
        self.counters["nan_quarantines"] += 1
        if req.nan_retried:
            self._finish_pending(
                req, FAILED,
                "non-finite logits persisted after one quarantine retry "
                "(prefill)", finished,
            )
            return
        log_event(
            "quarantine", rid=req.rid, phase="prefill",
            t=round(self._clock(), 6),
        )
        self._requeue([dataclasses.replace(
            req, gen=list(req.gen), nan_retried=True
        )])

    def _quarantine_slot(self, s: _Slot, row: int, finished,
                         phase: str = "decode") -> None:
        """Non-finite logits on an active row: free the row (neighbours
        untouched — per-row masking means its re-prefill reads only what
        it rewrites), requeue its CLEAN prefix for one fresh re-prefill,
        then FAILED on recurrence. ``phase`` labels the lifecycle log
        and failure reason (the paged engine's chunked prefill
        quarantines through here too)."""
        self.counters["nan_quarantines"] += 1
        if s.nan_retried:
            self._finish_slot(
                s, FAILED,
                "non-finite logits persisted after one quarantine retry "
                f"({phase})", finished,
            )
            return
        log_event(
            "quarantine", rid=s.rid, phase=phase, row=row,
            t=round(self._clock(), 6),
        )
        self._requeue([
            self._pending_from_slot(s, bump=False, nan_retried=True)
        ])

    def _dispatch(self, kind, params, group_pendings, finished, *args):
        """Run ``kind`` with the engine cache spliced in at its donated
        argnum, consulting the fault injector around the call. Returns
        (tokens, bad) as host arrays, or None after a RECOVERED failure.

        Any failure — the program raising, or the result dropped in
        transit — consumed the donated cache, so every in-flight row's
        K/V is gone: recovery converts them ALL to resume entries
        (re-prefilled from tokens-so-far on a later tick), charges one
        retry against each, and backs off exponentially; queued requests
        are untouched. ``dispatch_retries`` consecutive failures raise
        ``DispatchFailure`` with the state already consistent."""
        cache_at = self.CACHE_ARGNUM[kind] - 1  # args exclude params here
        args = list(args)
        args[cache_at] = self._take_cache()
        inj = self._injector
        # The span holds the call, the device's time and the one host
        # sync; recovery from a failed call runs after it has closed.
        # Inside it ``engine.launch.<kind>`` is the call alone and
        # ``engine.sync.<kind>`` the read-back of its outputs: the device
        # ops between them are the program.
        with self.timers.span("engine.dispatch." + kind):
            try:
                if inj is not None:
                    inj.before_dispatch(kind, self._ticks)
                # Programs return (tokens, ..., bad, cache): the spec
                # step carries the per-row accept lengths between tokens
                # and the sentinel; the injector hooks see (tokens, bad)
                # whichever program ran.
                with self.timers.span("engine.launch." + kind):
                    *outs, cache = self.program(kind)(params, *args)
                if inj is not None:
                    tok, bad = inj.after_dispatch(
                        kind, self._ticks, outs[0], outs[-1]
                    )
                    outs = [tok, *outs[1:-1], bad]
            except Exception as err:
                # Exception, not BaseException: KeyboardInterrupt/
                # SystemExit must abort the serving loop, not masquerade
                # as a transient device fault and get retried.
                failure = err
            else:
                self._cache = cache
                self._fail_streak = 0
                with self.timers.span("engine.sync." + kind):
                    # repolint: allow(blocking-sync-in-tick) — the
                    # adjudicated dispatch-boundary read: the scheduler
                    # needs this tick's tokens and sentinel ON HOST to
                    # route/retire rows before it can build the next
                    # dispatch, so exactly one sync per tick is the design
                    # (everything upstream stays async; the cache stays on
                    # device).
                    return tuple(np.asarray(o) for o in outs)
        self._recover_dispatch_failure(
            kind, failure, group_pendings or [], finished
        )
        return None

    def _recover_dispatch_failure(self, kind, err, group_pendings,
                                  finished) -> None:
        self.counters["dispatch_failures"] += 1
        self._fail_streak += 1
        log_event(
            "dispatch_fail", kind=kind, tick=self._ticks,
            streak=self._fail_streak, error=type(err).__name__,
            t=round(self._clock(), 6),
        )
        lost = []
        for s in self._slots:
            if s is not None:
                lost.append(self._pending_from_slot(s, bump=True))
                self._on_slot_freed(s)
        self._slots = [None] * self.slots
        lost += [
            dataclasses.replace(q, gen=list(q.gen), retries=q.retries + 1)
            for q in group_pendings
        ]
        kept = []
        for q in lost:
            if q.retries > self.request_retries:
                self._finish_pending(
                    q, FAILED,
                    f"dispatch failed ({type(err).__name__}) and the "
                    f"request exhausted its {self.request_retries} "
                    "fault-resume retries", finished,
                )
            else:
                self.counters["resumes"] += 1
                kept.append(q)
        self._requeue(kept)
        if (
            self.dispatch_retries is not None
            and self._fail_streak > self.dispatch_retries
        ):
            raise DispatchFailure(
                f"{self._fail_streak} consecutive dispatch failures "
                f"(> dispatch_retries {self.dispatch_retries}); engine "
                "state is consistent — every in-flight request was "
                "requeued (or FAILED past its retry budget); snapshot() "
                "and rebuild, or step again later"
            ) from err
        if self._fail_streak > 0 and self.retry_backoff_s > 0:
            self._sleep(
                self.retry_backoff_s * (2 ** (self._fail_streak - 1))
            )

    def _maybe_retire(self, row: int, finished: list[int]) -> None:
        s = self._slots[row]
        hit_eos = s.eos_id is not None and s.generated[-1] == s.eos_id
        if len(s.generated) < s.max_new and not hit_eos:
            return
        # Retirement is pure host bookkeeping: the row's K/V stays in
        # place (dirty) and the next admission masks it out.
        self._slots[row] = None
        self._on_slot_freed(s)
        self._finish_slot(s, DONE, "", finished)

    def _on_slot_freed(self, s: _Slot) -> None:
        """Hook: called whenever an occupied slot leaves the slot list
        (retire / abort / expire / quarantine / dispatch-failure
        conversion). The dense engine has nothing to do — a freed row's
        K/V just sits dirty in its own row; the paged subclass releases
        the row's page references here."""

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict[str, Any]:
        """Uniform engine-state snapshot: scheduler occupancy (queue
        depth, active rows, free slots) + page-pool pressure (None on
        non-paged engines — same keys everywhere, so the router's
        admission scoring reads one schema regardless of which engine
        backs a replica) + a copy of the monotonic ``counters`` and of
        the ``timers`` (count/total_s/max_s per span of the tick, and
        per request ``queue_wait`` / ``request.prefill`` /
        ``request.decode`` on the engine clock). Pure host bookkeeping;
        never dispatches."""
        free_slots = sum(1 for s in self._slots if s is None)
        by_tier = {name: 0 for name in PRIORITIES}
        for q in self._queue:
            by_tier[TIER_NAME[q.tier]] += 1
        return {
            "engine": type(self).__name__,
            "role": self.role,
            "device_ids": self.device_ids(),
            "queue_depth": len(self._queue),
            "queue_depth_by_tier": by_tier,
            "slots": self.slots,
            "active_rows": self.slots - free_slots,
            "free_slots": free_slots,
            "pool_pages": None,
            "free_pages": None,
            "pages_in_use": None,
            "session_pinned_pages": None,
            "sessions": None,
            "prefix_hit_rate": None,
            "allocatable_pages": None,
            "prefix_queries": None,
            "prefix_hits": None,
            "evictions": None,
            "kv_quant": "none",
            "paged_decode_impl": None,
            "state_step_impl": None,
            # what one cache position costs across all layers, in the
            # family's own page layout (per-head K and V, or one latent),
            # and what a ROW costs whatever its depth (recurrent state)
            "kv_bytes_per_position": self._bytes_per_position(),
            "state_bytes_per_row": decode.serving(
                self.cfg).state_bytes_per_row,
            "speculative_k": self.speculative_k,
            "spec_accept_rate": _spec_accept_rate(self.counters),
            "counters": dict(self.counters),
            "timers": self.timers.snapshot(),
        }

    def compile_count(self) -> int:
        """Total compiled executables across both programs: ONE
        decode(_spec)_step + one prefill per (bucket, group) shape
        served. The churn tests assert this stays flat across
        admissions and retirements at a fixed slot count."""
        return sum(p._cache_size() for p in self._programs.values())

    def _bytes_per_position(self) -> int:
        """K+V bytes one GLOBAL cache position costs across all layers
        (see ``_kv_bytes_per_position``; the paged subclass switches the
        figure when its pool is quantized)."""
        return _kv_bytes_per_position(self.cfg)

    def cache_hbm_bytes(self) -> dict[str, int]:
        """Allocated KV-cache HBM (the dense engine preallocates
        slots x max_len positions whether rows are deep or not — the
        number the paged engine's pool is benched against)."""
        n = self.slots * self.max_len
        b = n * self._bytes_per_position()
        return {"allocated": b, "peak_in_use": b}

    def example_args(self, kind: str, params, *, bucket: int | None = None,
                     group: int = 1, cache: decode.Cache | None = None):
        """Example argument tuple for lowering/auditing ``kind`` — the
        shapes ``step`` dispatches with. ``cache=None`` allocates a
        fresh one (callers doing real dispatches should pass
        ``self._take_cache()`` and pocket the returned buffer)."""
        if cache is None:
            cache = self._new_cache()
        if kind == "prefill":
            b = bucket or (
                self.buckets.buckets[0] if self.buckets.buckets else 4
            )
            npad = next(g for g in self._groups if g >= group)
            return (
                params,
                jnp.zeros((npad, b), jnp.int32),
                jnp.ones((npad,), jnp.int32),
                jnp.zeros((npad,), jnp.int32),
                cache,
                jnp.ones((npad,), jnp.bool_),
                jnp.ones((npad,), jnp.float32),
                jnp.full((npad,), self.cfg.vocab_size, jnp.int32),
                jnp.full((npad,), 2.0, jnp.float32),
                jnp.zeros((npad, self._key_words), jnp.uint32),
            ) + self._lora_dispatch_args(np.zeros((npad,), np.int32))
        if kind == "decode_step":
            b = self.slots
            return (
                params,
                jnp.zeros((b,), jnp.int32),
                cache,
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.ones((b,), jnp.bool_),
                jnp.ones((b,), jnp.float32),
                jnp.full((b,), self.cfg.vocab_size, jnp.int32),
                jnp.full((b,), 2.0, jnp.float32),
                jnp.zeros((b, self._key_words), jnp.uint32),
            ) + self._lora_dispatch_args(np.zeros((b,), np.int32))
        if kind == "decode_spec_step":
            b, width = self.slots, self.speculative_k + 1
            return (
                params,
                jnp.zeros((b, width), jnp.int32),
                cache,
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.ones((b,), jnp.bool_),
                jnp.ones((b,), jnp.float32),
                jnp.full((b,), self.cfg.vocab_size, jnp.int32),
                jnp.full((b,), 2.0, jnp.float32),
                jnp.zeros((b, self._key_words), jnp.uint32),
                jnp.zeros((b,), jnp.int32),
            ) + self._lora_dispatch_args(np.zeros((b,), np.int32))
        raise KeyError(f"unknown batched program kind {kind!r}")

    def verify_donation(self, params) -> dict[str, dict]:
        """Prove the slot cache actually aliases in/out of every batched
        program this engine dispatches (strict mode of the donation
        audit) — the engine-side twin of ``DecodeEngine.verify_donation``.
        A rejected alias would double-buffer the whole (slots, max_len)
        cache EVERY TOKEN."""
        from pytorch_distributed_tpu.analysis.audit import check_donation

        params = self._place_params(params)
        stats_all: dict[str, dict] = {}
        for kind in self._program_kinds():
            args = self.example_args(kind, params)
            compiled = self.program(kind).lower(*args).compile()
            findings, stats = check_donation(
                compiled.as_text(), args, (self.CACHE_ARGNUM[kind],),
                strict=True,
            )
            stats_all[kind] = stats
            if findings:
                raise RuntimeError(
                    f"batched engine program {kind!r} ({self.mode}): "
                    "donated slot KV cache does not fully alias in the "
                    f"compiled executable — {findings[0].message}"
                )
        return stats_all


@dataclasses.dataclass
class _PagedSlot(_Slot):
    """One occupied row of the PAGED slot batch. Extends ``_Slot`` with
    the row's page bookkeeping and chunked-prefill progress: ``pos``
    doubles as the prefill cursor (next position to prefill) until it
    reaches ``prefill_len``, after which the row is decode-ready and
    ``pos`` means what it means on the dense engine (next KV write
    offset). Dataclass-inheritance ordering forces defaults here; the
    engine always fills them at admission."""

    prefix: np.ndarray | None = None  # prompt + resume tokens to prefill
    prefill_len: int = 0  # len(prefix)
    table: np.ndarray | None = None  # [max_pages] int32 page ids (0=scratch)
    pids: list = dataclasses.field(default_factory=list)  # pages held
    n_pages: int = 0  # allocated table entries
    prefill_keydata: np.ndarray | None = None  # key for the final chunk draw
    resume_base: int = 0  # len(resume gen) riding ahead of fresh tokens
    chain_key: str = ""  # prefix-cache chain key at pos (1 digest/publish)
    # a family with a window group of pages: the row's window table is the
    # second half of ``table``, by absolute page number; it holds pages
    # wfirst .. wnext - 1 and those before point at the scratch page
    wfirst: int = 0
    wnext: int = 0

    @property
    def ready(self) -> bool:
        return self.pos >= self.prefill_len


@dataclasses.dataclass
class KVHandoff:
    """One finished prefill leaving a PREFILL worker (disaggregated
    serving): the device pages (+ block-table order, + per-row int8
    scale leaves riding the same tree) and every host field a decode
    worker needs to continue the row BIT-IDENTICALLY to a colocated
    run. ``entry`` doubles as the fault fallback: it is the ordinary
    PR-6 resume entry for the same row, so a handoff that never
    completes (either side dying) degrades to the existing
    resume/failover path with zero new machinery."""

    entry: Any            # _Pending resume entry (fault fallback + host fields)
    pages: Any            # device tree, per leaf [L, max_pages, ...]
    n_pages: int          # real (non-padding) table entries
    pos: int              # committed depth (== prefill_len on export)
    fold: int             # the row's PRNG fold cursor
    generated: list       # resume gen + the final-chunk sampled token
    prefill_len: int
    resume_base: int
    page_size: int
    max_pages: int
    kv_quant: str
    src_rid: int          # engine-local rid on the SOURCE engine
    useful_bytes: int     # n_pages x page_size x bytes/position
    wire_bytes: int       # padded tree bytes actually shipped
    export_s: float       # device time of the kv_export gather


class PagedBatchedDecodeEngine(BatchedDecodeEngine):
    """Continuous batching over a PAGED KV cache: the block-pool refactor
    of ``BatchedDecodeEngine`` (ROADMAP direction 1 — the vLLM move).

    The dense engine's ``(slots, max_len)`` cache charges every row
    O(max_len) HBM and O(max_len) attention regardless of its depth.
    Here the cache is a flat pool of fixed-size PAGES —
    ``[L, pool_pages, page_size, Hkv*D]`` — and each row holds a BLOCK
    TABLE of page ids instead of a dedicated row. Three consequences,
    all machine-checked:

    - **HBM scales with the pool, not slots x max_len**: ``slots`` can
      exceed what uniform-max_len rows would fit, because real rows are
      rarely max_len deep. Pool exhaustion mid-decode PREEMPTS the
      youngest active request (clean resume entry, re-admitted when
      pages free — "queued last, preempted first"), so overcommit
      degrades to queueing, never to a hang or corruption; admission
      additionally defers when the pool cannot cover a prompt.
    - **Prefix sharing**: identical prompt prefixes are stored ONCE
      (serving/block_pool.py: chunk-chained sha1 keys, refcounted pages,
      LRU retention after the last reference drops), copy-on-write by
      construction — shared pages are never written, forks land on
      private pages. Hit counts ride the lifecycle log and
      ``pool.stats``.
    - **Chunked prefill**: an admission is fed through the tick in
      ``prefill_chunk``-token chunks (one chunk per row per tick), so a
      long prompt never stalls in-flight rows for its whole prefill —
      the per-tick prefill cost is bounded by chunk x group, and decode
      ticks interleave. The chunk is the prefill compile shape (no
      prompt buckets: compile set = groups x ONE chunk shape + one
      decode step).

    Everything traced stays fixed-shape: block tables are [slots,
    max_pages] int32 OPERANDS (values change per tick, shapes never),
    staged with a dispatch's other per-row operands in ONE int32 array
    and one host-to-device transfer (serving/staged.py), so
    the PR-5 zero-steady-state-compile contract and the PR-6 fault
    model (quarantine, dispatch recovery, snapshot/replay) carry over
    unchanged — a failed dispatch consumed the donated POOL, so recovery
    additionally resets the block pool and prefix cache (the content the
    cache keys pointed at is gone). For a family that has a dense
    [B, max_len] cache too (gpt2, llama) attention defaults to the
    pure-XLA ``gather_pages`` fallback (bit-identical math to the dense
    engine — the paged-vs-dense token-equality pins in
    tests/test_serving_paged.py rely on it); on TPU,
    ``paged_attention="kernel"`` dispatches the Pallas paged-attention
    decode kernel (ops/paged_kernel.py), whose per-row cost scales with
    the row's depth. A family that has none (kimi_k2, granitemoehybrid)
    defaults to ``"auto"``: its decode step reads the pool through its
    kernel (ops/latent_paged_kernel.py, ops/paged_kernel.py) on a TPU and
    through the gathered window elsewhere (``stats()["paged_decode_impl"]``
    says which). The same setting decides how a family with per-row
    recurrent state advances it in the decode step: where the pages'
    kernel runs, one pass over each live row's state where it lies
    (ops/ssm_kernel.py), else plain XLA (``stats()["state_step_impl"]``:
    ``kernel`` / ``kernel_interpret`` / ``xla``; None without such state).

    **Page groups** (a family whose ``decode.Serving.window`` is set: some
    layers attend a sliding window): a second ``BlockPool`` (``wpool``,
    ``window_pool_pages``; left unset, ``slots`` x the most one row holds
    + the scratch page) feeds a second block table a row, handed to the
    programs beside the first ([slots, 2 max_pages]). Admission takes the
    prompt's pages of the full group and the first chunk's of the window
    group; every chunk and decode step grows both; after each, the window
    pages no query to come can see go back to their pool
    (``block_pool.first_kept_page``) and their table entries to the scratch
    page. Either group running dry preempts as the one pool does. Such a
    family takes no prefix hit, and preemption, ``snapshot``/``restore``
    rebuild its window pages by re-prefill from position 0.

    Knobs: ``page_size`` (tokens per KV page; must divide ``max_len``),
    ``pool_pages`` (pool capacity incl. the reserved scratch page 0;
    default = dense-equivalent ``slots * max_len/page_size + 1``),
    ``prefill_chunk`` (chunked-prefill quantum; page-multiple dividing
    ``max_len``, default = largest such <= 64).

    **Speculation on pages** (``speculative_k`` — see
    ``BatchedDecodeEngine``): rejection rollback is just truncating the
    row's depth. The verify window writes K/V for all k+1 lanes, but
    every write lands at positions >= the row's committed ``pos`` —
    strictly past any shared-prefix or session-pinned page (those cover
    positions < the row's first private chunk), so the sha1
    chunk-chained prefix cache never sees speculative state; committed
    lanes occupy the row's private tail pages (grown best-effort, never
    by preemption — ``_grow_for_drafts``), rejected lanes are masked
    garbage overwritten by later writes, and lanes past the table
    redirect to the scratch page. With int8 pages the per-token scales
    make rollback free: appending (and re-appending over garbage) can
    never re-quantize a neighbouring token. Multi-token verify windows
    use the XLA gather fallback even under ``paged_attention="kernel"``
    (the Pallas kernel is single-query; a multi-query twin is future
    surface).
    """

    # kv_import is the ONLY kv-handoff program that donates: it scatters
    # imported pages into this worker's pool in place. kv_export is a
    # pure gather and deliberately does NOT donate (the source pool must
    # stay valid until the router confirms the import landed — see
    # ``export_handoff``), so it has no entry here. Its argnums count
    # the program's own operands (kv programs take no params). The three
    # tick programs take ``(params, staged, cache, *lora_tree)``: a build's
    # per-row operands ride one int32 array (serving/staged.py).
    CACHE_ARGNUM = {
        "prefill": 2, "decode_step": 2, "decode_spec_step": 2,
        "kv_import": 2,
    }

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        slots: int,
        max_len: int,
        page_size: int = 16,
        pool_pages: int | None = None,
        prefill_chunk: int | None = None,
        paged_attention: str | None = None,
        kv_quant: str = "none",
        mesh_cfg: MeshConfig | None = None,
        session_pin_budget_pages: int | None = None,
        batch_admit_free_frac: float = 0.25,
        role: str = "colocated",
        window_pool_pages: int | None = None,
        **kw,
    ) -> None:
        if page_size < 1 or max_len % page_size:
            raise ValueError(
                f"page_size ({page_size}) must be a positive divisor of "
                f"max_len ({max_len}): the block table addresses exactly "
                "max_len/page_size pages per row, and a ragged final "
                "page would silently truncate the last "
                f"{max_len % page_size if page_size >= 1 else 0} cache "
                "positions — pick page_size from the divisors of max_len"
            )
        # What the family asks of an engine (``decode.Serving``: the
        # engine reads no family's name). What it cannot be served with is
        # refused with the family's own reason; a mesh here, before the
        # base class tries to place the family's parameters on it.
        self._family = decode.serving(cfg)
        self._unserved = self._family.unserved
        if ("mesh" in self._unserved and mesh_cfg is not None
                and mesh_cfg.num_devices > 1):
            raise NotImplementedError(self._unserved["mesh"])
        super().__init__(
            cfg, slots=slots, max_len=max_len, buckets=None,
            mesh_cfg=mesh_cfg, **kw,
        )
        self.page_size = int(page_size)
        self.max_pages = max_len // page_size
        if prefill_chunk is None:
            # Largest page-multiple <= 64 that divides max_len. The
            # chunk is BOTH the prefill quantum (per-tick prefill work
            # is bounded by chunk x group) and the prefix-sharing
            # granularity (block_pool caches chunk-chained prefixes), so
            # the default leans small; deployments with long shared
            # system prompts and long arrivals tune it per traffic.
            prefill_chunk = page_size
            while (
                prefill_chunk * 2 <= min(64, max_len)
                and max_len % (prefill_chunk * 2) == 0
            ):
                prefill_chunk *= 2
        if (
            prefill_chunk < page_size
            or prefill_chunk % page_size
            or max_len % prefill_chunk
        ):
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must be a multiple of "
                f"page_size ({page_size}) that divides max_len "
                f"({max_len}) — chunk starts are page-aligned and the "
                "padded final chunk must stay inside the row's table"
            )
        self.chunk = int(prefill_chunk)
        if pool_pages is None:
            pool_pages = slots * self.max_pages + 1
        if pool_pages < self.max_pages + 1:
            raise ValueError(
                f"pool_pages ({pool_pages}) must be >= max_len/page_size "
                f"+ 1 = {self.max_pages + 1} (one full-length row plus "
                "the scratch page), or a single deep request could "
                "never be served"
            )
        self.pool_pages = int(pool_pages)
        from pytorch_distributed_tpu.serving.block_pool import (
            BlockPool,
            window_pages_bound,
        )

        self.pool = BlockPool(self.pool_pages, self.page_size, self.chunk)
        # A window group of pages beside the pool (``decode.Serving.window``;
        # 0: there is none and a row has one table).
        self._window = self._family.window
        self.wpool = None
        if self._window:
            self._window_row_bound = window_pages_bound(
                self._window, self.chunk, self.page_size)
            if window_pool_pages is None:
                window_pool_pages = slots * self._window_row_bound + 1
            if window_pool_pages < self._window_row_bound + 1:
                raise ValueError(
                    f"window_pool_pages ({window_pool_pages}) must be >= "
                    f"{self._window_row_bound + 1}: what one row holds of "
                    f"the window group while a chunk is written (a window "
                    f"of {self._window}, chunks of {self.chunk}, pages of "
                    f"{self.page_size}) plus the scratch page"
                )
            self.window_pool_pages = int(window_pool_pages)
            self.wpool = BlockPool(
                self.window_pool_pages, self.page_size, self.chunk)
        elif window_pool_pages is not None:
            raise ValueError(
                f"window_pool_pages: the {cfg.family} family has no window "
                "group of pages")
        # a row's tables side by side, as the programs take them
        self._table_width = self.max_pages * (2 if self._window else 1)
        if paged_attention is None:
            # Left unset, the pages are read by a kernel wherever one can
            # run (the gather copies every row's whole table a layer,
            # whatever its depth) by a family that has no dense
            # [B, max_len] cache; one that has keeps the gather, which is
            # bit-identical to the dense engine's math (the paged-vs-dense
            # token equality of tests/test_serving_paged.py rests on it): a
            # family with no dense engine has nothing to be identical to.
            paged_attention = (
                "gather" if self._family.dense_cache else "auto"
            )
        if paged_attention == "auto":
            paged_attention = (
                "kernel" if jax.devices()[0].platform == "tpu"
                else "gather"
            )
        if paged_attention not in ("gather", "kernel", "kernel_interpret"):
            raise ValueError(
                f"paged_attention must be 'auto', 'gather', 'kernel' or "
                f"'kernel_interpret', got {paged_attention!r}"
            )
        self._paged_impl = paged_attention
        self.kv_quant = _check_quant_arg("kv_quant", kv_quant)
        # Disaggregation role (ROADMAP direction 1): "colocated" is the
        # historic engine (prefill + decode on one worker); "prefill"
        # runs chunked prefill only and parks finished rows for
        # ``export_handoff``; "decode" accepts rows only via
        # ``import_handoff``/``adopt`` and never prefills fresh prompts.
        self.role = _check_role(role)
        asked = {
            "kv_quant": self.kv_quant != "none",
            "weight_quant": self.weight_quant != "none",
            "adapters": bool(self.adapters),
            "speculative_k": bool(self.speculative_k),
            "handoff": self.role != "colocated",
        }
        for feature, why in self._unserved.items():
            if asked.get(feature):
                raise NotImplementedError(why)
        # Recurrent state a row, beside its pages (0: the cache is pages
        # alone): the state leaves are [L, slots + 1, ...], lane i of the
        # decode step is state row i, a prefill dispatch names its rows'
        # slots, and row ``slots`` is the scratch row.
        self._row_state = self._family.state_bytes_per_row
        # Neither rows with state nor rows with a window group take a prefix
        # another row cached: nothing is matched, published or pinned, and
        # ``prefix_queries`` stays 0.
        self._no_prefix = bool(self._row_state or self._window)
        # Where each per-row operand lies in the one int32 array a build
        # transfers (serving/staged.py), a layout per program kind; the
        # builds' transfers counted per kind (one a dispatch).
        self._layouts = paged_layouts(
            chunk=self.chunk, spec_width=self.speculative_k + 1,
            table_width=self._table_width, key_words=self._key_words,
            vocab=cfg.vocab_size,
            scratch_row=self.slots if self._row_state else None,
            tenants=self.adapters is not None,
        )
        for kind in self._program_kinds():
            self.counters[f"arg_transfers.{kind}"] = 0
            self.counters[f"sample_full_sort.{kind}"] = 0
        # A model that counts its work has its programs hand the counts
        # back between the tokens and the sentinel; they accumulate here
        # per program kind, beside what the engine itself knows of a
        # dispatch under the names the family's readers ask for
        # (``_count_dispatch``). Only the two plain programs carry counts.
        self._aux_counts = self._family.aux_counts
        for kind in ("prefill", "decode_step"):
            for name in self._aux_counts:
                self.counters[f"{name}.{kind}"] = 0
        for name in self._family.counters:
            self.counters[name] = 0
        self.counters["preemptions"] = 0
        self.counters["preempt_priority"] = 0
        self.counters["batch_yield_ticks"] = 0
        self.counters["handoffs_out"] = 0
        self.counters["handoffs_in"] = 0
        if not 0.0 <= batch_admit_free_frac <= 1.0:
            raise ValueError(
                f"batch_admit_free_frac must be in [0, 1], got "
                f"{batch_admit_free_frac} (the free-page fraction below "
                "which BATCH-tier requests stop admitting)"
            )
        self.batch_admit_free_frac = float(batch_admit_free_frac)
        from pytorch_distributed_tpu.serving.session import SessionTracker

        # Session retention pins at most half the pool by default and
        # evict_idle sheds loudly past the budget. Pins can still cover
        # capacity a queued request needs when every pinned session has
        # a turn in flight (inflight pins are unevictable) — _admit's
        # no-live-rows go-around below keeps that from stalling the
        # queue for good.
        self._sessions = SessionTracker(
            self.pool,
            pin_budget_pages=(
                (self.pool_pages - 1) // 2
                if session_pin_budget_pages is None
                else session_pin_budget_pages
            ),
            clock=self._clock,
        )
        log_event(
            "pool_build",
            quant=self.kv_quant,
            pool_pages=self.pool_pages,
            page_size=self.page_size,
            prefill_chunk=self.chunk,
            slots=self.slots,
            pool_hbm_bytes=(
                self.pool_pages * self.page_size
                * _kv_bytes_per_position(cfg, self.kv_quant)
            ),
        )

    # -- cache -------------------------------------------------------------

    def _cache_pspec(self) -> dict:
        """Per-leaf PartitionSpecs for the paged cache under TP: every
        leaf shards its last axis. The value pools' is the head-major
        merged Hkv*D (a shard is Hkv/tp whole heads), the int8 layout's
        scale pools' is Hkv (scales live with their heads)."""
        from jax.sharding import PartitionSpec as P

        names = ("k", "v")
        if self.kv_quant == "int8":
            names += ("k_scale", "v_scale")
        return {name: P(None, None, None, "tensor") for name in names}

    def _new_cache(self) -> decode.Cache:
        self.counters["cache_allocs"] += 1
        if self.mode == "tp":
            full = decode.init_paged_cache(
                self.cfg, self.pool_pages, self.page_size,
                kv_quant=self.kv_quant,
            )
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = jax.tree.map(
                lambda s: NamedSharding(self._mesh, s),
                self._cache_pspec(),
                is_leaf=lambda x: isinstance(x, P),
            )
            return jax.device_put(full, sharding)
        cache = decode.init_paged_cache(
            self.cfg, self.pool_pages, self.page_size, n_kv=self._n_kv,
            kv_quant=self.kv_quant,
            rows=self.slots if self._row_state else None,
            window_pool_pages=(
                self.window_pool_pages if self._window else None),
        )
        if self.device is not None:
            # Committed inputs pin every jitted program's outputs to the
            # same chip (see the dense engine's _new_cache).
            cache = jax.device_put(cache, self.device)
        return cache

    def _bytes_per_position(self) -> int:
        return _kv_bytes_per_position(self.cfg, self.kv_quant)

    def cache_hbm_bytes(self) -> dict[str, int]:
        """Allocated pool HBM + the peak actually referenced by live
        rows (pages_in_use x page_size positions), to set against the
        dense engine's slots x max_len."""
        per = self._bytes_per_position()
        out = {
            "allocated": self.pool_pages * self.page_size * per,
            "peak_in_use": (
                self.pool.stats["peak_pages_in_use"] * self.page_size * per
            ),
        }
        if self._window:
            wper = _kv_bytes_per_position(self.cfg, group="window")
            out["allocated"] += (
                self.window_pool_pages * self.page_size * wper)
            out["peak_in_use"] += (
                self.wpool.stats["peak_pages_in_use"] * self.page_size * wper)
        return out

    def stats(self) -> dict[str, Any]:
        """The uniform snapshot with the paged fields filled in: page
        pressure (free/in-use against the pool) is the second admission
        signal the router weighs next to queue depth — closing the gap
        where ``pool.stats`` was a paged-only side channel."""
        out = super().stats()
        ps = self.pool.stats
        out.update(
            # pool_pages is the EFFECTIVE page capacity: a quantized
            # pool provisioned at byte-equal HBM holds ~4x the f32
            # pages, and that real capacity is the router's page-
            # pressure denominator (pages_in_use / pool_pages) — scoring
            # in bytes would starve-exclude a quantized replica that
            # still has page headroom (regression-pinned in
            # tests/test_serving_quant.py).
            pool_pages=self.pool_pages,
            free_pages=self.pool.free_pages(),
            pages_in_use=self.pool.pages_in_use(),
            # Session retention's capacity cost: pages held ONLY by a
            # pin. The router's least-loaded scoring adds these to page
            # pressure, so a session-heavy replica is deprioritized
            # BEFORE it starts preempting for its pinned residents.
            session_pinned_pages=self.pool.pinned_pages(),
            sessions=len(self._sessions),
            prefix_hit_rate=round(
                ps["prefix_hits"] / max(1, ps["prefix_queries"]), 4
            ),
            # The raw counts behind the two figures above: what the
            # allocator can deliver once it evicts (the router sheds on
            # free_pages alone), and monotonic counts an operator can
            # difference over a window.
            allocatable_pages=self.pool.allocatable_pages(),
            prefix_queries=ps["prefix_queries"],
            prefix_hits=ps["prefix_hits"],
            evictions=ps["evictions"],
            kv_quant=self.kv_quant,
            # what the decode program reads the pages through, and what it
            # advances a row's recurrent state through (ops/ssm_kernel.py
            # wherever the pages' kernel runs; None: no such state)
            paged_decode_impl=self._paged_impl,
            state_step_impl=(
                {"gather": "xla"}.get(self._paged_impl, self._paged_impl)
                if self._row_state else None),
        )
        out["counters"]["session_evictions"] = self._sessions.evictions
        if self._window:
            out.update(
                window_pool_pages=self.window_pool_pages,
                window_free_pages=self.wpool.free_pages(),
                window_pages_in_use=self.wpool.pages_in_use(),
            )
        return out

    # -- programs ----------------------------------------------------------

    def _forward_paged(self, params, ids, cache, pos, tables, lora=None,
                       **plain):
        """``decode.forward`` on the paged pool. ``plain``: what the two
        plain programs pass (``live``, ``logits_index``); a model that
        counts its work (``self._aux_counts``) then hands the counts back
        as a third value."""
        kwargs = {
            "block_tables": tables,
            "paged_impl": self._paged_impl,
            "kv_quant": self.kv_quant,
            **plain,
        }
        if self.mode == "tp":
            kwargs["tensor_axis"] = "tensor"
        if lora:
            kwargs["lora"] = lora
        if plain and self._aux_counts:
            kwargs["return_aux"] = True
        return decode.forward(params, ids, self.cfg, cache, pos, **kwargs)

    def _bodies(self):
        """The three paged program bodies. Same traced-everything
        discipline as the dense engine, plus the [B, max_pages] block
        tables as int32 operands; the NaN sentinel and sampling are
        shared with the dense bodies so they can never drift. Each takes
        ``(params, staged, cache, *lora_tree)`` and first splits
        ``staged`` back into its per-row operands (serving/staged.py):
        the build transferred them as one array."""
        layouts = self._layouts

        def operands(kind, staged, lora_tree):
            a = layouts[kind].split(staged)
            lora = (lora_tree[0], a["tenants"]) if lora_tree else ()
            return a, lora

        def sample(a, last, keys):
            return decode.sample_token_rows(
                last, a["greedy"], a["t"], keys, a["k"], a["p"])

        def prefill(params, staged, cache, *lora_tree):
            # One CHUNK per row: tokens chunks[:, :valid] run at
            # positions start..start+valid-1 (pad positions write
            # garbage past the write point into the row's own padded
            # extent — the dense dirty-cache discipline at page
            # granularity). The sampled token only matters for rows on
            # their final chunk; the host discards the rest. The logits
            # are those of valid-1; a model that counts its work counts
            # the chunk's real tokens only.
            a, lora = operands("prefill", staged, lora_tree)
            chunks, valid = a["chunks"], a["valid"]
            lanes = jnp.arange(chunks.shape[1], dtype=jnp.int32)
            live = lanes[None] < valid[:, None]
            rows = {}
            if self._row_state:
                # the SLOT of each row of the group (the state leaves are
                # per slot); the group's padding points at the scratch row
                # and holds no token
                live &= (a["slot_rows"] < self.slots)[:, None]
                rows = {"state_rows": a["slot_rows"]}
            logits, cache, *aux = self._forward_paged(
                params, chunks, cache, a["start"], a["tables"], lora,
                live=live, logits_index=valid - 1, **rows,
            )
            last = logits[:, 0]
            tok = sample(a, last, jax.random.wrap_key_data(a["keydata"]))
            return (tok, *aux, decode.nonfinite_rows(last), cache)

        def decode_step(params, staged, cache, *lora_tree):
            # (a free or mid-prefill lane's table is all scratch page:
            # page 0, which no live row is ever given)
            a, lora = operands("decode_step", staged, lora_tree)
            tables = a["tables"]
            logits, cache, *aux = self._forward_paged(
                params, a["toks"][:, None], cache, a["pos"], tables, lora,
                live=(tables[:, :1] != 0),
            )
            last = logits[:, -1]
            keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(a["keydata"]), a["folds"]
            )
            tok = sample(a, last, keys)
            return (tok, *aux, decode.nonfinite_rows(last), cache)

        def decode_spec_step(params, staged, cache, *lora_tree):
            # The paged verify window: k+1 tokens write through the
            # row's block table — committable lanes land on its private
            # tail pages (the host grew the table to cover them), lanes
            # past the table redirect to the scratch page
            # (decode._write), and the shared-prefix pages are
            # untouchable by construction (all writes land at
            # >= the row's first private position).
            a, lora = operands("decode_spec_step", staged, lora_tree)
            return self._spec_verify(
                self._forward_paged(
                    params, a["toks"], cache, a["pos"], a["tables"], lora
                ),
                a["toks"], a["folds"], a["greedy"], a["t"], a["k"], a["p"],
                a["keydata"], a["n_draft"],
            )

        return {
            "prefill": prefill,
            "decode_step": decode_step,
            "decode_spec_step": decode_spec_step,
        }

    def _kv_bodies(self):
        """The two kv-handoff program bodies (disaggregated serving):
        params-free page movers, generic over the cache tree so int8
        pools ship their per-token scale leaves alongside the values.
        Padded table entries are 0, so export gathers (and import
        scatters) scratch-page garbage on the unused lanes —
        garbage-by-design, exactly like a free row's decode lane."""

        def kv_export(cache, table):
            # [L, pool_pages, ...] -> [L, max_pages, ...] per leaf: one
            # row's pages in table order. NOT donated — the source pool
            # stays live until the handoff is confirmed complete.
            return {kk: vv[:, table] for kk, vv in cache.items()}

        def kv_import(pages, table, cache):
            # Scatter one exported row into this pool at the freshly
            # allocated page ids (donates the pool — in-place scatter).
            # table duplicates (the 0-padding) overlap-write only the
            # scratch page.
            return {
                kk: cache[kk].at[:, table].set(pages[kk]) for kk in cache
            }

        return {"kv_export": kv_export, "kv_import": kv_import}

    def _check_program_kind(self, kind: str) -> None:
        if kind in _KV_PROGRAM_KINDS:
            return
        super()._check_program_kind(kind)

    def program(self, kind: str):
        self._check_program_kind(kind)
        prog = self._programs.get(kind)
        if prog is not None:
            return prog
        kv = kind in _KV_PROGRAM_KINDS
        body = self._kv_bodies()[kind] if kv else self._bodies()[kind]
        ca = self.CACHE_ARGNUM.get(kind)
        donate = () if ca is None else (ca,)
        if self.mode == "plain":
            prog = jax.jit(body, donate_argnums=donate)
        else:  # tp: head-sharded page pool, everything else replicated
            from jax.sharding import PartitionSpec as P

            from pytorch_distributed_tpu.utils.compat import shard_map

            cache_spec = self._cache_pspec()
            # ``staged`` replicated; the adapter tree as the dense engine
            # shards it (its tenant slots ride ``staged``)
            tick = (self._p_specs, P(), cache_spec) + self._lora_in_specs()[:1]
            specs = {
                "prefill": tick,
                "decode_step": tick,
                "decode_spec_step": tick,
                # Pages ship head-sharded exactly like the pool they
                # came from / land in: each TP shard moves its own
                # slice, no collectives (NO_COLLECTIVES-pinned in the
                # audit registry). No LoRA operands — kv programs are
                # params-free.
                "kv_export": (cache_spec, P()),
                "kv_import": (cache_spec, P(), cache_spec),
            }[kind]
            out_specs = {
                "decode_spec_step": (P(), P(), P(), cache_spec),
                "kv_export": cache_spec,
                "kv_import": cache_spec,
            }.get(kind, (P(), P(), cache_spec))
            smapped = shard_map(
                body,
                mesh=self._mesh,
                in_specs=specs,
                out_specs=out_specs,
                check_vma=True,
            )
            prog = jax.jit(smapped, donate_argnums=donate)
        self._programs[kind] = prog
        return prog

    # -- sessions ----------------------------------------------------------

    def open_session(self) -> int:
        """Open one multi-turn chat session (serving/session.py):
        returns the sid ``submit(session=)`` takes. Turn N resubmits the
        conversation-so-far and pays ~one chunk of prefill via the
        pinned prefix cache; idle sessions past the pin budget are
        evicted loudly (their next turn pays a cold prefill)."""
        return self._sessions.open()

    def close_session(self, sid: int) -> None:
        """Close a session: its pins return to ordinary LRU retention
        (the chunks may still be hit until evicted). Unknown sids raise."""
        self._sessions.close(sid)

    def _session_checkin(self, session, prompt) -> int:
        if session is None:
            return 0
        return self._sessions.check_turn(session, prompt)

    def _session_begin(self, session, rid) -> None:
        if session is not None:
            self._sessions.begin_turn(session, rid)

    def _finish(self, rid, *args, **kw) -> None:
        # Every terminal state clears the session's in-flight marker (a
        # DONE turn already recorded its transcript via
        # ``_retire_session_turn``); non-session rids no-op.
        self._sessions.on_terminal(rid)
        super()._finish(rid, *args, **kw)

    def _retire_session_turn(self, s: _PagedSlot) -> None:
        """A session turn is retiring DONE: publish its DECODE-written
        full chunks (prefill already published the prompt's — the K/V
        of a generated token is the same pure function of its prefix,
        so these are sound cache entries; MUST run before the row's
        pages release so retention sees them resident), then hand the
        tracker the new transcript + the full chain to pin."""
        toks = self._partial_tokens(s.prompt, s.generated)
        if self._no_prefix:
            # nothing published, nothing to pin: the transcript alone
            self._sessions.on_turn_done(s.session, toks, [])
            return
        cp = self.chunk // self.page_size
        key = s.chain_key  # chain at the last prefill-published boundary
        for st in range(
            (s.prefill_len // self.chunk) * self.chunk,
            (s.pos // self.chunk) * self.chunk,
            self.chunk,
        ):
            first = st // self.page_size
            key = self.pool.register_chunk(
                toks, st, s.table[first: first + cp].tolist(),
                prev_key=key,
            )
        self._sessions.on_turn_done(
            s.session, toks, self.pool.chain_keys(toks, s.pos)
        )

    def _maybe_retire(self, row: int, finished: list[int]) -> None:
        s = self._slots[row]
        hit_eos = s.eos_id is not None and s.generated[-1] == s.eos_id
        if len(s.generated) < s.max_new and not hit_eos:
            return
        if s.session is not None:
            self._retire_session_turn(s)
        self._slots[row] = None
        self._on_slot_freed(s)
        self._finish_slot(s, DONE, "", finished)

    # -- scheduler ---------------------------------------------------------

    def _batch_headroom(self) -> bool:
        """BATCH-tier admission gate: only while at least
        ``batch_admit_free_frac`` of the pool is ALLOCATABLE (free or
        LRU-reclaimable — retired cached prefixes are headroom, not
        pressure) does throughput traffic admit — a batch backlog fills
        otherwise-idle capacity but never bids against interactive/
        standard traffic for a contended pool."""
        return (
            self.pool.allocatable_pages()
            >= self.batch_admit_free_frac * (self.pool_pages - 1)
        )

    def _admit(self, params, finished: list[int]) -> None:
        with self.timers.span("engine.admit"):
            free = [i for i, s in enumerate(self._slots) if s is None]
            # The queue is sorted ONCE and the order reused across
            # admissions (queue_key is static per request, so removals keep
            # it sorted); only a preemption's requeued victim invalidates
            # it. Pool state cannot change during a candidate scan, so the
            # batch-headroom gate — an O(cached-chunks) pool walk — is
            # evaluated at most once per scan.
            ordered = None
            blocked: set[int] = set()
            while self._queue:
                # Priority-ordered admission (scheduler.queue_key):
                # interactive first (earliest deadline within the tier),
                # then standard/batch in FIFO order — an all-standard queue
                # admits exactly like the pre-tier engine. BATCH entries are
                # SKIPPED (not blocking) while the pool lacks headroom.
                if ordered is None:
                    ordered = sorted(self._queue, key=self._queue_key)
                req = None
                headroom = None
                for cand in ordered:
                    if cand.rid in blocked:
                        continue
                    if cand.tier == TIER_RANK[BATCH]:
                        if headroom is None:
                            headroom = self._batch_headroom()
                        if not headroom:
                            continue
                    req = cand
                    break
                if req is None:
                    break
                if not free:
                    # No free slot: an INTERACTIVE arrival may preempt a
                    # strictly-lower-priority active row for its slot (and
                    # pages); everyone else waits for a retirement.
                    n0 = len(self._queue)
                    row = self._preempt_lower_priority(req.tier, finished)
                    if len(self._queue) != n0:
                        ordered = None
                    if row is None:
                        break
                    free.append(row)
                slot = self._try_allocate(req)
                while slot is None:
                    # Page shortage: idle-session pins break FIRST (cheap —
                    # the session just loses retention), then strictly-
                    # lower-priority actives are preempted for their pages.
                    # BATCH never breaks a pin: pinned pages are not the
                    # idle capacity batch is allowed to fill (the router
                    # scores them unavailable for the same reason) — a
                    # batch row this large waits for ordinary retirements.
                    if (
                        req.tier != TIER_RANK[BATCH]
                        and self._sessions.evict_idle()
                    ):
                        slot = self._try_allocate(req)
                        continue
                    n0 = len(self._queue)
                    row = self._preempt_lower_priority(req.tier, finished)
                    if len(self._queue) != n0:
                        ordered = None
                    if row is None:
                        break
                    free.append(row)
                    slot = self._try_allocate(req)
                if slot is None:
                    # Highest-priority admissible entry waits for pages
                    # (deferral, not a hang): decode keeps running and
                    # retirements free pages. With NO live rows nothing can
                    # ever retire — a head this large would stall the queue
                    # for good when the pages it needs are pinned by
                    # sessions whose own queued turns (the only thing that
                    # releases the pins) sit right behind it — so only then
                    # do later, smaller entries go around it this tick.
                    if any(s is not None for s in self._slots):
                        break
                    blocked.add(req.rid)
                    continue
                self._queue.remove(req)
                if ordered is not None:
                    ordered.remove(req)
                row = free.pop(0)
                self._slots[row] = slot
                self._stamp_row(slot.stamps)
                log_event(
                    "admit", rid=slot.rid, row=row,
                    cached_tokens=slot.pos or None,
                    resume_prefix=slot.resume_base or None,
                    priority=(
                        TIER_NAME[slot.tier]
                        if slot.tier != TIER_RANK[STANDARD] else None
                    ),
                    session=slot.session,
                    t=round(self._clock(), 6),
                )
        self._chunk_prefill_tick(params, finished)

    def _preempt_lower_priority(self, tier: int, finished) -> int | None:
        """Preempt the lowest-priority-then-youngest active row whose
        tier is STRICTLY below ``tier`` (admission-side preemption: an
        interactive arrival takes a batch row's slot/pages regardless of
        age; standard/batch arrivals never preempt — they wait for a
        retirement, exactly the pre-tier schedule). Returns the freed
        row index, or None when the arrival may not preempt or nothing
        outranked exists."""
        if tier != TIER_RANK[INTERACTIVE]:
            return None
        cands = [
            (preemption_key(s.tier, s.rid), i)
            for i, s in enumerate(self._slots)
            if s is not None and s.tier > tier
        ]
        if not cands:
            return None
        (_, rid), row = max(cands)
        s = self._slots[row]
        self._slots[row] = None
        self._on_slot_freed(s)
        self.counters["preempt_priority"] += 1
        log_event(
            "preempt_priority", rid=rid, row=row, depth=s.pos,
            victim_tier=TIER_NAME[s.tier], for_tier=TIER_NAME[tier],
            t=round(self._clock(), 6),
        )
        self._requeue([self._pending_from_slot(s, bump=False)])
        return row

    def _try_allocate(self, req: _Pending) -> _PagedSlot | None:
        """Build a slot for ``req`` if the pool can cover its prefill
        extent: shared prefix pages are acquired from the prefix cache
        (never for a quarantine retry — a poisoned row re-prefills from
        scratch on purpose), private pages allocated for the rest,
        rounded up to the chunk the padded final prefill writes."""
        prefix = self._partial_tokens(req.prompt, req.gen)
        plen = prefix.shape[0]
        if req.nan_retried or self._no_prefix:
            # (nor where rows hold recurrent state or a window group of
            # pages: a cached prefix's pages come without the state at its
            # end, or the window's positions before it, so nothing is
            # matched, published or pinned, and ``prefix_queries`` stays 0)
            cached, shared, chain_key = 0, [], ""
        else:
            cached, shared, chain_key = self.pool.match_prefix(
                prefix, plen - 1
            )
        ext = -(-plen // self.chunk) * self.chunk  # padded prefill extent
        fresh = self.pool.alloc(ext // self.page_size - len(shared))
        if fresh is None:
            # Deferred, not admitted: the match never happened as far as
            # the hit counters are concerned — a head-of-line request
            # retrying every tick must not inflate the committed stats.
            # (A quarantine retry never queried, so nothing to cancel.)
            if not (req.nan_retried or self._no_prefix):
                self.pool.cancel_match(cached, shared)
            return None
        wfresh = []
        if self._window:
            # the window group: the first chunk's pages now, the rest chunk
            # by chunk as the pages behind the window come back
            wfresh = self.wpool.alloc(
                min(ext, self.chunk) // self.page_size)
            if wfresh is None:
                self.pool.release(fresh)
                return None
        if cached:
            log_event(
                "prefix_hit", rid=req.rid, cached_tokens=cached,
                prompt_len=plen, t=round(self._clock(), 6),
                quant=self.kv_quant if self.kv_quant != "none" else None,
            )
        pids = list(shared) + fresh
        table = np.zeros((self._table_width,), np.int32)
        table[: len(pids)] = pids
        table[self.max_pages: self.max_pages + len(wfresh)] = wfresh
        if req.session is not None:
            # First admission of a session turn commits its prefix-hit
            # economics (preemption re-admissions are de-duplicated by
            # rid inside the tracker).
            self._sessions.note_admit(req.rid, cached, req.resub_len)
        return _PagedSlot(
            rid=req.rid, prompt=req.prompt, max_new=req.max_new,
            eos_id=req.eos_id, pos=cached, fold=len(req.gen),
            generated=list(req.gen), greedy=req.greedy,
            t=req.t, k=req.k, p=req.p, keydata=req.keydata,
            deadline=req.deadline, retries=req.retries,
            nan_retried=req.nan_retried,
            tier=req.tier, session=req.session,
            resub_len=req.resub_len, tenant_slot=req.tenant_slot,
            prefix=prefix, prefill_len=plen, table=table, pids=pids,
            n_pages=len(pids), prefill_keydata=req.prefill_keydata,
            resume_base=len(req.gen), chain_key=chain_key,
            stamps=req.stamps, wnext=len(wfresh),
        )

    def _chunk_prefill_tick(self, params, finished: list[int]) -> None:
        """Advance every mid-prefill row by ONE chunk (one grouped
        dispatch): long prompts trickle in across ticks while decode-
        ready neighbours keep generating — the chunked-prefill
        contract."""
        rows = [
            (i, s) for i, s in enumerate(self._slots)
            if s is not None and not s.ready
        ]
        if rows and any(
            s is not None and s.ready
            and s.tier == TIER_RANK[INTERACTIVE]
            for s in self._slots
        ):
            # BATCH prefill yields to interactive decode (the prefill
            # half of the decode-tick yield): while a latency-tier row
            # is generating, throughput rows do not inflate its ticks
            # with their chunk prefills. Deliberately NOT while the
            # interactive row is still mid-prefill: batch prefill
            # proceeding there keeps its pages held, which is what the
            # preempt-lowest path reclaims the moment the latency row
            # needs them. Bounded: interactive rows retire within
            # max_new ticks, then the backlog streams in. Standard rows
            # are untouched (the all-STANDARD schedule stays the
            # pre-tier one).
            rows = [
                (i, s) for i, s in rows
                if s.tier != TIER_RANK[BATCH]
            ]
        if rows and self._window:
            # each row's window table grown over the chunk it writes now
            # (a group run dry preempts for the pages: the rows still in
            # their slots afterwards are the ones that go)
            for i, s in rows:
                if self._slots[i] is s:
                    self._grow_window(i, s, s.pos + self.chunk, finished)
            rows = [(i, s) for i, s in rows if self._slots[i] is s]
        if not rows:
            return
        with self.timers.span("engine.build.prefill"):
            n = len(rows)
            npad = next(g for g in self._groups if g >= n)
            idx = list(range(n)) + [0] * (npad - n)
            staged = self._layouts["prefill"].new(npad)
            a = self._layouts["prefill"].views(staged)
            valid = a["valid"]
            for j, ii in enumerate(idx):
                _, s = rows[ii]
                v = min(self.chunk, s.prefill_len - s.pos)
                a["chunks"][j, :v] = s.prefix[s.pos : s.pos + v]
                valid[j] = v
                a["start"][j] = s.pos
                a["tables"][j] = s.table
                self._stage_row(a, j, s, s.prefill_keydata)
            if self._row_state:
                # The slot each row of the group sits in. The padding
                # repeats row 0: in a family whose K and V are a function
                # of the tokens alone it rewrites row 0's pages with the
                # same values; where rows carry state it is given the
                # scratch row (the layout's default) AND the scratch page,
                # since no state is carried in for it and its K and V
                # differ from row 0's.
                a["slot_rows"][:n] = [i for i, _ in rows]
                a["tables"][n:] = 0
            args = self._put_staged("prefill", staged)
        res = self._dispatch("prefill", params, [], finished, *args)
        if res is None:
            return  # recovery converted every in-flight row already
        toks, *aux, bad = res
        with self.timers.span("engine.settle.prefill"):
            self._count_dispatch("prefill", aux, int(valid[:n].sum()))
            for j in range(n):
                row, s = rows[j]
                if bad[j]:
                    self._slots[row] = None
                    self._on_slot_freed(s)
                    self._quarantine_slot(
                        s, row, finished, phase="prefill"
                    )
                    continue
                v = min(self.chunk, s.prefill_len - s.pos)
                if v == self.chunk and not self._no_prefix:
                    # A full chunk lies entirely inside the prefix:
                    # publish its pages for prefix sharing (clean chunks
                    # only — a flagged row never contaminates the cache).
                    # The chain key rides the slot, so each publish is
                    # one digest.
                    cp = self.chunk // self.page_size
                    first = s.pos // self.page_size
                    s.chain_key = self.pool.register_chunk(
                        s.prefix, s.pos,
                        s.table[first : first + cp].tolist(),
                        prev_key=s.chain_key,
                    )
                s.pos += v
                if s.pos >= s.prefill_len:
                    s.generated.append(int(toks[j]))
                    self._stamp_first_token(s.stamps)
                    if self.role == "prefill":
                        # The row is now handoff-eligible: it parks here
                        # (pages held) until the router pumps it to a
                        # decode worker. bytes = the pages a handoff
                        # will ship.
                        log_event(
                            "prefill_done", rid=s.rid,
                            prompt_len=s.prefill_len, pages=s.n_pages,
                            bytes=(
                                s.n_pages * self.page_size
                                * self._bytes_per_position()
                            ),
                            t=round(self._clock(), 6),
                        )
                    self._maybe_retire(row, finished)
        self._release_behind_window()

    def _grow_for_drafts(self, s: _PagedSlot, n: int) -> int:
        """Best-effort block-table growth covering the row's draft
        window (committable positions pos..pos+n need REAL pages — an
        accepted draft's K/V becomes the row's cache). Returns how many
        drafts are actually covered. Never preempts a live row and
        never breaks a session pin: drafts are an optimisation, so page
        pressure just shrinks the window (the verify step still commits
        its one guaranteed token on the already-covered page; lanes
        past the shrunk window ride table-zero lanes onto the scratch
        page). This is also why speculative width does not change the
        router's page-pressure accounting: at most these few
        transiently-held tail pages per row, already counted by
        ``pages_in_use`` like any other allocation."""
        while s.n_pages * self.page_size <= s.pos + n:
            got = self.pool.alloc(1)
            if got is None:
                n = s.n_pages * self.page_size - s.pos - 1
                break
            s.table[s.n_pages] = got[0]
            s.pids += got
            s.n_pages += 1
        return max(0, n)

    def _decode_tick_spec(self, params, finished: list[int]) -> None:
        """The paged speculative tick: the dense ``_decode_tick_spec``
        plus block tables, the tier-yield schedule, and draft-window
        page growth. Rollback is depth truncation: a rejected draft's
        K/V stays as garbage past the row's committed ``pos`` on the
        row's PRIVATE tail page — the prefix cache and any session-
        pinned pages never see speculative state."""
        with self.timers.span("engine.build.decode"):
            interactive_live = any(
                s is not None and s.tier == TIER_RANK[INTERACTIVE]
                for s in self._slots
            )
            self._ensure_decode_pages(finished, skip_batch=interactive_live)
            ready = []
            yielded = False
            for i, s in enumerate(self._slots):
                if s is None or not s.ready:
                    continue
                if interactive_live and s.tier == TIER_RANK[BATCH]:
                    yielded = True
                    continue
                ready.append((i, s))
            if yielded:
                self.counters["batch_yield_ticks"] += 1
            if not ready:
                return
            staged = self._layouts["decode_spec_step"].new(self.slots)
            a = self._layouts["decode_spec_step"].views(staged)
            n_draft = a["n_draft"]
            for i, s in ready:
                drafts = self._draft_tokens(s)
                drafts = drafts[: self._grow_for_drafts(s, len(drafts))]
                a["toks"][i, 0] = s.generated[-1]
                a["toks"][i, 1 : 1 + len(drafts)] = drafts
                n_draft[i] = len(drafts)
                self._stage_step_row(a, i, s)
            args = self._put_staged("decode_spec_step", staged)
        res = self._dispatch(
            "decode_spec_step", params, None, finished, *args
        )
        if res is None:
            return
        out, n_acc, bad = res
        with self.timers.span("engine.settle.decode"):
            for i, s in ready:
                if bad[i]:
                    self._slots[i] = None
                    self._on_slot_freed(s)
                    self._quarantine_slot(s, i, finished)
                    continue
                self._commit_spec(
                    i, s, out[i], int(n_acc[i]), int(n_draft[i]), finished
                )

    def _decode_tick(self, params, finished: list[int]) -> None:
        if self.role == "prefill":
            # A PREFILL worker never decodes: finished-prefill rows park
            # (ready, pages held) until the router's handoff pump ships
            # them to a decode worker (``export_handoff``). _maybe_retire
            # already retired any max_new==1 row at its final chunk.
            return
        if self.speculative_k:
            return self._decode_tick_spec(params, finished)
        with self.timers.span("engine.build.decode"):
            # BATCH decode yields to a live interactive row (the decode
            # half of the chunk-prefill yield below): while a latency-tier
            # request occupies a slot, throughput rows sit out the tick —
            # their lanes stay zeroed (table 0 -> the scratch page), so the
            # interactive tick's working set shrinks to the latency rows'
            # own pages instead of streaming every batch row's cache
            # through it. A skipped tick recomputes nothing (the row's
            # operands are a pure function of its own state), so batch
            # tokens stay bit-equal — just later. Bounded: interactive
            # rows retire within max_new ticks, then batch streams again.
            # STANDARD rows never yield (the all-STANDARD schedule is the
            # pre-tier one).
            interactive_live = any(
                s is not None and s.tier == TIER_RANK[INTERACTIVE]
                for s in self._slots
            )
            self._ensure_decode_pages(finished, skip_batch=interactive_live)
            ready = []
            yielded = False
            for i, s in enumerate(self._slots):
                if s is None or not s.ready:
                    continue
                if interactive_live and s.tier == TIER_RANK[BATCH]:
                    yielded = True
                    continue
                ready.append((i, s))
            if yielded:
                self.counters["batch_yield_ticks"] += 1
            if not ready:
                return
            staged = self._layouts["decode_step"].new(self.slots)
            a = self._layouts["decode_step"].views(staged)
            for i, s in ready:
                # Free AND mid-prefill rows keep the defaults: table 0 ->
                # the scratch page, so their garbage write/read never
                # touches a live row's pages (and slot 0 is the zero
                # adapter).
                a["toks"][i] = s.generated[-1]
                self._stage_step_row(a, i, s)
            args = self._put_staged("decode_step", staged)
        res = self._dispatch("decode_step", params, None, finished, *args)
        if res is None:
            return
        out, *aux, bad = res
        with self.timers.span("engine.settle.decode"):
            self._count_dispatch("decode_step", aux, len(ready), ready)
            for i, s in ready:
                if bad[i]:
                    self._slots[i] = None
                    self._on_slot_freed(s)
                    self._quarantine_slot(s, i, finished)
                    continue
                s.generated.append(int(out[i]))
                s.pos += 1
                s.fold += 1
                self._maybe_retire(i, finished)
        self._release_behind_window()

    def _stage_row(self, a, j: int, s: _PagedSlot, keydata) -> None:
        """Row ``j``'s sampling state (and tenant slot) into the build's
        views ``a`` of its staged array."""
        a["greedy"][j] = s.greedy
        a["t"][j], a["k"][j], a["p"][j] = s.t, s.k, s.p
        a["keydata"][j] = keydata
        if "tenants" in a:
            a["tenants"][j] = s.tenant_slot

    def _stage_step_row(self, a, i: int, s: _PagedSlot) -> None:
        """What a decode or verify step stages for ready row ``i`` beside
        its tokens."""
        a["pos"][i] = s.pos
        a["tables"][i] = s.table
        a["folds"][i] = s.fold
        self._stage_row(a, i, s, s.keydata)

    def _put_staged(self, kind: str, staged: np.ndarray) -> tuple:
        """The build's ONE host-to-device transfer (``engine.put.<kind>``,
        counted in ``arg_transfers.<kind>``): the dispatch's operands after
        ``params``, the cache's place left None for ``_dispatch`` and the
        adapter tree (uploaded once a registration) after it. A dispatch
        whose rows ask the sampler for the full-vocabulary sort, by the
        rule its program applies, counts in ``sample_full_sort.<kind>``."""
        a = self._layouts[kind].views(staged)
        if decode.needs_full_sort(
                a["greedy"] != 0, a["k"], self.cfg.vocab_size):
            self.counters["sample_full_sort." + kind] += 1
        with self.timers.span("engine.put." + kind):
            self.counters["arg_transfers." + kind] += 1
            return (jax.device_put(staged, self.device), None,
                    *self._lora_tree())

    def _lora_tree(self) -> tuple:
        return () if self.adapters is None else (self.adapters.device_tree(),)

    def _count_dispatch(self, kind: str, aux, tokens: int, ready=()) -> None:
        """One dispatch onto the counters of its program kind: the counts
        the program handed back (``aux``: none, or one array of them), and
        of what the engine itself knows those the family asks for by name
        (``decode.Serving.counters``)."""
        for name, n in zip(self._aux_counts, aux[0] if aux else ()):
            self.counters[f"{name}.{kind}"] += int(n)
        if not self._family.counters:
            return
        did = {f"moe_tokens.{kind}": tokens}
        if kind == "decode_step":
            # each ready row's token at pos attends positions 0..pos; a
            # gathered window holds every row's whole table
            reach = sum(s.pos + 1 for _, s in ready)
            did.update(
                state_rows_advanced=len(ready), kv_positions_read=reach,
                latent_positions_read=reach,
                kv_positions_window=self.slots * self.max_len,
                latent_positions_window=self.slots * self.max_len)
            did["kv_positions_read.full"] = reach
            if self._window:
                # a layer of the window group: the window's keys a row;
                # and, sampled here, what that group's pages hold of all
                # rows beside what some row's window needs of it
                did["kv_positions_read.window"] = sum(
                    min(s.pos + 1, self._window) for _, s in ready)
                live = [s for s in self._slots if s is not None]
                did["window_positions_held"] = self.page_size * sum(
                    s.wnext - s.wfirst for s in live)
                did["window_positions_needed"] = sum(
                    min(s.pos + 1, self._window) for s in live)
        for name in self._family.counters:
            if name in did:
                self.counters[name] += did[name]

    def _ensure_decode_pages(
        self, finished: list[int], skip_batch: bool = False
    ) -> None:
        """Grow each decode-ready row's table to cover its next write.
        Pool exhaustion preempts the YOUNGEST other active request
        (admitted last -> preempted first): its clean prefix requeues as
        a resume entry — no retry charge, no token loss — and its pages
        come back to the pool. ``skip_batch``: batch rows yielding this
        tick don't advance, so growing their tables now could only fire
        a needless preemption under pressure."""
        for i in range(self.slots):
            # Read the LIVE slot list each iteration: a preemption fired
            # for an earlier row may have freed this one, and growing a
            # dead slot would leak its page (and could preempt a live
            # row to feed a corpse).
            s = self._slots[i]
            if s is None or not s.ready:
                continue
            if skip_batch and s.tier == TIER_RANK[BATCH]:
                continue
            if s.pos // self.page_size >= s.n_pages:
                pid = self._take_page(self.pool, i, s, finished)
                if pid is None:
                    continue  # the row itself yielded its pages
                s.table[s.n_pages] = pid
                s.pids.append(pid)
                s.n_pages += 1
            if self._window:
                self._grow_window(i, s, s.pos + 1, finished)

    def _take_page(self, pool, i: int, s: _PagedSlot, finished) -> int | None:
        """One page of ``pool`` (a group's) for row ``i``, preempting for it
        where the group has run dry; None where row ``i`` itself was
        preempted (it is the lowest-priority occupant)."""
        while True:
            got = pool.alloc(1)
            if got is not None:
                return got[0]
            # Retention must never deadlock allocation: idle-session
            # pins break (loudly) before any live row is preempted.
            if self._sessions.evict_idle():
                continue
            others = [
                o.tier for o in self._slots
                if o is not None and o.rid != s.rid
            ]
            if others and max(others) < s.tier:
                # Every neighbour strictly outranks this row: IT is
                # the lowest-priority occupant, so it yields its own
                # pages (a batch row must never evict interactive
                # state to keep growing) — clean resume entry, like
                # any other preemption.
                self._preempt_row(i)
                return None
            if not self._preempt_one(exclude_rid=s.rid, finished=finished):
                from pytorch_distributed_tpu.serving.lifecycle import (
                    PagePoolExhausted,
                )

                raise PagePoolExhausted(
                    f"no KV page available for rid {s.rid} at depth "
                    f"{s.pos} and nothing left to preempt — "
                    f"pool_pages={pool.pool_pages} cannot hold one "
                    "row this deep (construction should have "
                    "rejected this configuration)"
                )

    def _grow_window(self, i: int, s: _PagedSlot, upto: int,
                     finished) -> bool:
        """Grow row ``i``'s window table over positions < ``upto`` (a chunk
        about to be written, a decode step's one position), a page at a
        time from the window group; False where the row was preempted for
        the pages it lacks."""
        mp = self.max_pages
        while s.wnext * self.page_size < min(upto, self.max_len):
            pid = self._take_page(self.wpool, i, s, finished)
            if pid is None:
                return False
            s.table[mp + s.wnext] = pid
            s.wnext += 1
        return True

    def _release_behind_window(self) -> None:
        """After a dispatch: every row's window-group pages that no query
        still to come can see (those wholly behind ``pos - window + 1``, the
        row's next query being at ``pos``) go back to their pool, and their
        table entries to the scratch page."""
        if not self._window:
            return
        from pytorch_distributed_tpu.serving import block_pool

        mp = self.max_pages
        with self.timers.span("engine.release_window"):
            for s in self._slots:
                if s is None:
                    continue
                keep = min(block_pool.first_kept_page(
                    s.pos, self._window, self.page_size), s.wnext)
                if keep <= s.wfirst:
                    continue
                gone = s.table[mp + s.wfirst: mp + keep]
                self.wpool.release(gone.tolist())
                gone[:] = 0
                self.counters["window_pages_released"] += keep - s.wfirst
                s.wfirst = keep

    def _preempt_one(self, *, exclude_rid: int, finished) -> bool:
        # Preempt-lowest-priority-then-youngest (scheduler.py): the
        # victim is the active row with the MAX (tier rank, rid) — a
        # batch row goes before an interactive row regardless of age,
        # and an all-STANDARD batch recovers PR-8's preempt-youngest
        # exactly.
        cands = [
            (preemption_key(s.tier, s.rid), i)
            for i, s in enumerate(self._slots)
            if s is not None and s.rid != exclude_rid
        ]
        if not cands:
            return False
        self._preempt_row(max(cands)[1])
        return True

    def _preempt_row(self, row: int) -> None:
        """Convert one active row to a clean resume entry (no retry
        charge, pages released) — the shared tail of every preemption
        path."""
        s = self._slots[row]
        self._slots[row] = None
        self._on_slot_freed(s)
        self.counters["preemptions"] += 1
        log_event(
            "preempt", rid=s.rid, row=row, depth=s.pos,
            generated=len(s.generated) - s.resume_base,
            tier=(
                TIER_NAME[s.tier]
                if s.tier != TIER_RANK[STANDARD] else None
            ),
            t=round(self._clock(), 6),
        )
        self._requeue([self._pending_from_slot(s, bump=False)])

    def _on_slot_freed(self, s: _Slot) -> None:
        self.pool.release(s.pids)
        s.pids = []
        if self._window:
            mp = self.max_pages
            self.wpool.release(s.table[mp + s.wfirst: mp + s.wnext].tolist())
            s.wfirst = s.wnext = 0

    def _recover_dispatch_failure(self, kind, err, group_pendings,
                                  finished) -> None:
        # The donated page pool was consumed with the dispatch: its
        # content is gone, so every cached prefix chunk would alias
        # garbage. Reset the pool BEFORE base recovery (which may raise
        # DispatchFailure at the end) and zero the slots' page lists so
        # the freed-slot hook has nothing stale to release.
        for s in self._slots:
            if s is not None:
                s.pids = []
                s.wfirst = s.wnext = 0
        self.pool.reset()
        if self._window:
            self.wpool.reset()
        # Every pinned chunk's content died with the pool: drop the
        # pins (transcripts survive — the next turn re-pays prefill).
        self._sessions.on_pool_reset()
        super()._recover_dispatch_failure(
            kind, err, group_pendings, finished
        )

    # -- introspection / warmup --------------------------------------------

    def warmup(self, params) -> int:
        """Compile every prefill group shape plus the decode step (the
        whole steady-state compile set: chunked prefill has ONE token
        shape, so there is no bucket dimension to cover). Disaggregated
        roles additionally warm their side of the kv-handoff pair —
        export on PREFILL workers, import on DECODE workers — so a
        steady-state handoff compiles nothing."""
        if self.has_work():
            raise RuntimeError("warmup requires an idle engine")
        params = self._place_params(params)
        for g in self._groups:
            args = self.example_args(
                "prefill", params, group=g, cache=self._take_cache()
            )
            *_, cache = self.program("prefill")(*args)
            self._cache = cache
        self._rewarm_first_prefill(params)
        step_kind = self._program_kinds()[-1]
        args = self.example_args(
            step_kind, params, cache=self._take_cache()
        )
        *_, cache = self.program(step_kind)(*args)
        self._cache = cache
        if self.role == "prefill":
            cache, table = self.example_args(
                "kv_export", params, cache=self._take_cache()
            )
            jax.block_until_ready(self.program("kv_export")(cache, table))
            self._cache = cache  # export does not donate
        elif self.role == "decode":
            # Twice, threading the output back in: the first call's
            # donated pool is a decode_step OUTPUT, but every steady
            # import consumes a previous import's output — whose layout
            # can hash differently (the _rewarm_first_prefill trick for
            # the handoff path; pinned by the disagg compile tests).
            for _ in range(2):
                pages, table, cache = self.example_args(
                    "kv_import", params, cache=self._take_cache()
                )
                self._cache = self.program("kv_import")(
                    self._place_handoff_pages(pages), table, cache
                )
            # The first decode tick after an import consumes the
            # import's output pool — cover THAT input layout too.
            args = self.example_args(
                step_kind, params, cache=self._take_cache()
            )
            *_, cache = self.program(step_kind)(*args)
            self._cache = cache
        return self.compile_count()

    def example_args(self, kind: str, params, *, bucket: int | None = None,
                     group: int = 1, cache: decode.Cache | None = None):
        """Example argument tuple for lowering/auditing ``kind``.
        ``bucket`` is accepted for API parity with the dense engine and
        ignored — the chunk is the only prefill token shape."""
        if cache is None:
            cache = self._new_cache()
        mp = self.max_pages
        if kind in self._layouts:
            rows = (next(g for g in self._groups if g >= group)
                    if kind == "prefill" else self.slots)
            # placed as a build places it (``_put_staged``), so the warmed
            # executables are the ones steady serving hits
            staged = jax.device_put(self._layouts[kind].new(rows), self.device)
            return (params, staged, cache, *self._lora_tree())
        if kind == "kv_export":
            # kv programs are params-free: ``params`` is accepted (and
            # ignored) for signature parity with every other kind.
            return (cache, jnp.zeros((mp,), jnp.int32))
        if kind == "kv_import":
            pages = {
                kk: jnp.zeros(
                    (vv.shape[0], mp) + tuple(vv.shape[2:]), vv.dtype
                )
                for kk, vv in cache.items()
            }
            return (pages, jnp.zeros((mp,), jnp.int32), cache)
        raise KeyError(f"unknown batched program kind {kind!r}")

    # -- disaggregation: kv handoff ----------------------------------------

    def submit(self, prompt, max_new_tokens: int, **kw) -> int:
        if self.role == "decode":
            raise ValueError(
                "this engine is a DECODE worker: it accepts rows only "
                "via import_handoff (finished prefills) or adopt "
                "(failover resume entries) — route fresh prompts to a "
                "prefill or colocated worker"
            )
        return super().submit(prompt, max_new_tokens, **kw)

    def handoff_ready(self) -> list[int]:
        """Engine rids of rows parked on this PREFILL worker with their
        prefill finished — the rows ``export_handoff`` can ship. Empty
        on every other role (colocated rows decode in place)."""
        if self.role != "prefill":
            return []
        return [
            s.rid for s in self._slots
            if s is not None and s.ready
        ]

    def export_handoff(self, rid: int) -> KVHandoff:
        """Gather one parked row's KV pages off the pool (kv_export —
        warmed, zero steady-state compiles) and package everything a
        decode worker needs to continue it bit-identically. READ-ONLY:
        the row stays live (pages held, fault model intact) until
        ``complete_handoff`` confirms the import landed — a destination
        dying mid-handoff costs nothing but the gather."""
        if "handoff" in self._unserved:
            raise NotImplementedError(self._unserved["handoff"])
        s = next(
            (x for x in self._slots if x is not None and x.rid == rid),
            None,
        )
        if s is None:
            raise KeyError(f"no active row with rid {rid} to hand off")
        if not s.ready:
            raise ValueError(
                f"rid {rid} is mid-prefill (pos {s.pos} < "
                f"{s.prefill_len}) — only finished prefills hand off"
            )
        t0 = time.perf_counter()
        cache = self._take_cache()
        pages = self.program("kv_export")(cache, jnp.asarray(s.table))
        self._cache = cache  # not donated: the pool buffer stays valid
        jax.block_until_ready(pages)
        export_s = time.perf_counter() - t0
        wire = sum(
            v.size * v.dtype.itemsize for v in jax.tree.leaves(pages)
        )
        return KVHandoff(
            entry=self._pending_from_slot(s, bump=False),
            pages=pages, n_pages=s.n_pages, pos=s.pos, fold=s.fold,
            generated=list(s.generated), prefill_len=s.prefill_len,
            resume_base=s.resume_base, page_size=self.page_size,
            max_pages=self.max_pages, kv_quant=self.kv_quant,
            src_rid=s.rid,
            useful_bytes=(
                s.n_pages * self.page_size * self._bytes_per_position()
            ),
            wire_bytes=int(wire), export_s=export_s,
        )

    def complete_handoff(self, rid: int) -> None:
        """The destination confirmed the import: release the source
        row WITHOUT a terminal result — ownership (and the client's
        rid mapping, which the router owns) moved to the destination
        engine. The freed pages go back to this worker's pool."""
        for i, s in enumerate(self._slots):
            if s is not None and s.rid == rid:
                self._slots[i] = None
                self._on_slot_freed(s)
                self.pool.note_handoff_out(s.n_pages)
                self.counters["handoffs_out"] += 1
                return
        raise KeyError(f"no active row with rid {rid} to complete")

    def can_import_handoff(self, h: KVHandoff) -> bool:
        """Cheap host-side gate the router's handoff pump scores
        targets with: a free slot row plus allocatable pool headroom
        for the row's pages (LRU-evictable cached prefixes count —
        they are reclaimable, not pressure)."""
        return (
            self.role != "prefill"
            and "handoff" not in self._unserved
            and any(s is None for s in self._slots)
            and self.pool.allocatable_pages() >= h.n_pages
            and h.page_size == self.page_size
            and h.max_pages == self.max_pages
            and h.kv_quant == self.kv_quant
        )

    def _place_handoff_pages(self, pages):
        """Commit an exported pages tree to THIS engine's placement:
        the wire hop of the handoff. The source committed the tree to
        ITS device(s); re-committing keeps every kv_import operand on
        one placement (and keeps the import's compiled signature
        identical to the one ``warmup`` built — a sharding-hash
        mismatch here would be a steady-state compile)."""
        if self.mode == "tp":
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = jax.tree.map(
                lambda sp: NamedSharding(self._mesh, sp),
                self._cache_pspec(),
                is_leaf=lambda x: isinstance(x, P),
            )
            return jax.device_put(pages, sharding)
        dev = self.device if self.device is not None else jax.devices()[0]
        return jax.device_put(pages, dev)

    def import_handoff(
        self, h: KVHandoff, finished: list[int] | None = None
    ) -> int | None:
        """Land one exported row in this worker's pool (kv_import —
        donated in-place scatter, warmed on DECODE workers) and seat it
        as a decode-ready slot under a fresh local rid. Returns the new
        rid, or None when the import could not land (no headroom, or
        the scatter dispatch failed and was RECOVERED — pool reset,
        in-flight rows converted to resume entries exactly like any
        failed dispatch; terminal rids from that recovery land in
        ``finished``). The source row is untouched either way until
        ``complete_handoff``."""
        if "handoff" in self._unserved:
            raise NotImplementedError(self._unserved["handoff"])
        if self.role == "prefill":
            raise ValueError(
                "a PREFILL worker cannot import handoffs — it only "
                "exports them"
            )
        if (
            h.page_size != self.page_size
            or h.max_pages != self.max_pages
            or h.kv_quant != self.kv_quant
        ):
            raise ValueError(
                "kv_handoff geometry mismatch: source pages are "
                f"(page_size={h.page_size}, max_pages={h.max_pages}, "
                f"kv_quant={h.kv_quant!r}) but this engine is "
                f"(page_size={self.page_size}, max_pages="
                f"{self.max_pages}, kv_quant={self.kv_quant!r}) — "
                "disaggregated fleets must share the page geometry"
            )
        q = h.entry
        if len(q.prompt) + q.max_new > self.max_len:
            raise ValueError(
                f"handed-off entry needs {len(q.prompt) + q.max_new} "
                f"cache positions but this engine's max_len is "
                f"{self.max_len}"
            )
        row = next(
            (i for i, s in enumerate(self._slots) if s is None), None
        )
        if row is None:
            return None
        pids = self.pool.alloc_for_handoff(h.n_pages)
        if pids is None:
            return None
        table = np.zeros((self.max_pages,), np.int32)
        table[: h.n_pages] = pids
        pages = self._place_handoff_pages(h.pages)
        try:
            cache = self.program("kv_import")(
                pages, jnp.asarray(table), self._take_cache()
            )
        except Exception as err:
            # The donated pool was consumed with the failed scatter:
            # same recovery as any failed dispatch (pool reset, rows to
            # resume entries). May raise DispatchFailure past the
            # streak budget — the router treats that as replica death.
            self.pool.release(pids)
            self._recover_dispatch_failure(
                "kv_import", err, [],
                finished if finished is not None else [],
            )
            return None
        self._cache = cache
        rid = self._next_rid
        self._next_rid += 1
        self._slots[row] = _PagedSlot(
            rid=rid, prompt=q.prompt, max_new=q.max_new, eos_id=q.eos_id,
            pos=h.pos, fold=h.fold, generated=list(h.generated),
            greedy=q.greedy, t=q.t, k=q.k, p=q.p, keydata=q.keydata,
            deadline=q.deadline, retries=q.retries,
            nan_retried=q.nan_retried, tier=q.tier,
            # Sessions are engine-local (pinned pages live on the
            # source); a handed-off turn finishes as a plain request,
            # exactly like adopt().
            session=None, resub_len=0, tenant_slot=q.tenant_slot,
            prefix=self._partial_tokens(
                q.prompt, list(q.gen)[: h.resume_base]
            ),
            prefill_len=h.prefill_len, table=table, pids=list(pids),
            n_pages=h.n_pages, prefill_keydata=q.prefill_keydata,
            resume_base=h.resume_base, chain_key="", stamps=q.stamps,
        )
        self.counters["handoffs_in"] += 1
        return rid


@functools.lru_cache(maxsize=None)
def shim_engine(
    cfg: ModelConfig, max_len: int, mesh_cfg: MeshConfig | None = None
) -> DecodeEngine:
    """Engine cache backing the models/decode.generate* compat shims:
    exact-length buckets (identical compile behaviour to the old
    monolithic entry — one prefill compile per distinct prompt length)
    and one engine per (cfg, max_len, mesh). Cache pooling is OFF so a
    shim call frees its cache like the old jit-internal path did — these
    engines live forever in this lru_cache, and a pooled cache per
    distinct (max_len, batch) would grow device memory with request
    diversity. Real serving loops should construct a DecodeEngine
    directly with a fixed max_len and power-of-two buckets (pooling on)."""
    return DecodeEngine(
        cfg, max_len=max_len, mesh_cfg=mesh_cfg, pool_caches=False
    )
