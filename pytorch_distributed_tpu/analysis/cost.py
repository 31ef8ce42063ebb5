"""Static FLOPs / HBM-traffic / wire-bytes costing over optimized HLO.

PR 15 made *memory* static and contractual (analysis/memory.py pins
peak-live bytes per registry program); this module does the same for
*throughput*. Every perf win since PR 3 — prefetch windows, bucketed
reduce-scatter, the paged pool, int8 pages, speculative verify — is at
bottom a claim about three per-step quantities:

- **FLOPs** executed (compute-bound ceiling),
- **HBM bytes moved** (bandwidth-bound ceiling),
- **collective wire bytes** (the ICI term multi-chip projections price).

All three are derivable from the scheduled HLO text the audit pass
already parses, so a regression that doubles a matmul, upcasts the int8
pool, or un-coalesces a bucketed collective moves a pinned number
loudly in CI — no hardware in the loop.

Cost model (and its honest limits):

- **FLOPs**: ``dot``/``convolution`` count contraction math
  (2 x output elements x contracted elements); reduce-class ops
  (``reduce``, ``reduce-window``, ``scatter``, ``select-and-scatter``,
  ``sort``) count their largest operand (a reduction touches every
  input element once); every other arithmetic op counts its output
  elements (one FLOP per element — transcendentals undercount, but the
  pinned ceilings are contracts, not cycle counts); data movement
  (copies, slices, gathers, converts, collectives) counts zero.
- **HBM bytes**: operand bytes + output bytes per instruction,
  dtype-aware via ``memory.shape_bytes`` (an int8 page pool shows its
  real 0.3125x traffic). Fusions count ONCE at the fusion boundary —
  internal producers never materialize. Views (``get-tuple-element``,
  ``bitcast``, ``tuple``) and parameters/constants move nothing at
  their own program point. In-place ``dynamic-update-slice`` is
  deliberately over-counted at destination size (a monotone proxy,
  same stance as the liveness scan).
- **Loop scoping**: a ``while`` contributes its body + condition cost
  multiplied by the static trip count XLA recorded
  (``backend_config={"known_trip_count":...}`` — present on every
  registry program's loops). A while with NO derivable trip count is
  counted ONCE and reported loudly (``unknown_trip_whiles`` /
  ``lower_bound``): the estimate becomes a lower bound, never a
  silently-dropped loop. ``conditional`` takes the max over branches.
- **Wire bytes** (per participating chip, ring accounting — the same
  convention as ``profiling/comm_model``, cross-checked in
  tests/test_cost_analysis.py): with group size N and payload B,
  all-gather / reduce-scatter / all-to-all move B x (N-1)/N, an
  all-reduce moves 2 x B x (N-1)/N (reduce-scatter + all-gather), a
  collective-permute / broadcast moves B. Group size comes from the
  instruction's ``replica_groups`` (explicit or iota form); a
  single-member group — a mesh=1 collective — moves ZERO bytes.

What this is NOT: a cycle-accurate simulator. The numbers feed two
consumers: the pinned ``CostBudget`` ceilings (exact, frozen, loud) and
the roofline projection (``project_step_time`` — max of compute-bound
and bandwidth-bound time at a configurable ``RooflineSpec``, with the
wire term exposed or overlapped per the program's
``CollectiveBudget.async_min_compute`` contract). Real step time on real
hardware sits above both; the projection is the hardware-independent
floor that turns "tok/s regressed" into "which of the three resources
grew".
"""

from __future__ import annotations

import dataclasses
import re

from pytorch_distributed_tpu.analysis.hlo import HLO_COLLECTIVES
from pytorch_distributed_tpu.analysis.memory import (
    HloComputation,
    HloModule,
    parse_module,
    shape_bytes,
    shape_dims,
    shape_elements,
)

# Ops that neither compute nor move bytes at their own program point:
# metadata, views, and buffer-table bookkeeping.
_FREE_OPCODES = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
    "rng-get-and-update-state", "get-dimension-size",
})

# Pure data movement: bytes count, FLOPs do not. (convert IS bandwidth —
# the int8 dequant read — but no math in the roofline sense.)
_MOVE_OPCODES = frozenset({
    "copy", "copy-start", "copy-done", "reshape", "broadcast",
    "transpose", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "gather", "pad", "reverse", "iota", "convert",
    "bitcast-convert", "real", "imag", "custom-call", "infeed",
    "outfeed", "send", "send-done", "recv", "recv-done", "domain",
})

# Reduction-class ops: FLOPs at the largest operand (every input element
# participates once), not the (much smaller) output.
_REDUCE_OPCODES = frozenset({
    "reduce", "reduce-window", "scatter", "select-and-scatter", "sort",
})

_TRIP_COUNT_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONTRACT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,\s]*)\}")
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,\s]*)\}")
_REPLICA_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


def _collective_base(opcode: str) -> str | None:
    """Base collective opcode for an instruction opcode, or None.
    ``-start`` forms count (they carry the payload); ``-done`` forms do
    not (their traffic was counted at the start)."""
    for base in sorted(HLO_COLLECTIVES, key=len, reverse=True):
        if opcode == base or opcode == base + "-start":
            return base
        if opcode == base + "-done":
            return None
    return None


def _is_collective(opcode: str) -> bool:
    return any(
        opcode == b or opcode == b + "-start" or opcode == b + "-done"
        for b in HLO_COLLECTIVES
    )


def group_size(attrs: str, default: int = 1) -> int:
    """Participant count of a collective from its ``replica_groups``
    attribute: explicit ``{{0,1,2,3}, ...}`` (size of the first group —
    XLA requires uniform groups) or iota ``[G,S]<=[T]`` (S). ``default``
    (the module's num_partitions) covers the
    all-devices-implicit ``replica_groups={}`` form."""
    m = _REPLICA_GROUPS_RE.search(attrs)
    if m:
        ids = [p for p in m.group(1).split(",") if p.strip()]
        return max(1, len(ids))
    m = _REPLICA_GROUPS_IOTA_RE.search(attrs)
    if m:
        return max(1, int(m.group(2)))
    return max(1, default)


def collective_wire_bytes(
    base: str, payload_bytes: int, n: int
) -> int:
    """Per-chip ring-transfer bytes of one collective instruction.

    ``payload_bytes``: the full (unsharded-along-the-collective) tensor
    bytes — output for gather-like ops, operand for reduce-scatter.
    A single-member group (n == 1) moves nothing.
    """
    if n <= 1:
        return 0
    frac = (n - 1) / n
    if base == "all-reduce":
        return int(2 * payload_bytes * frac)
    if base in ("all-gather", "all-to-all", "ragged-all-to-all",
                "reduce-scatter"):
        return int(payload_bytes * frac)
    if base in ("collective-permute", "collective-broadcast"):
        return int(payload_bytes)
    return int(payload_bytes * frac)


def _dot_flops(instr) -> int:
    """2 x output elements x contracted elements, from the inline lhs
    operand type + ``lhs_contracting_dims``. Falls back to output
    elements when the dump omits either (never silently zero)."""
    out = shape_elements(instr.shape)
    m = _CONTRACT_DIMS_RE.search(instr.attrs)
    lhs_dims = (
        shape_dims(instr.operand_shapes[0])
        if instr.operand_shapes else None
    )
    if not m or lhs_dims is None:
        return 2 * out
    contracted = 1
    for idx in (int(p) for p in m.group(1).split(",") if p.strip()):
        if 0 <= idx < len(lhs_dims):
            contracted *= lhs_dims[idx]
    return 2 * out * contracted


_WINDOW_SIZE_RE = re.compile(r"window=\{[^}]*size=([x\d]+)")


def _conv_flops(instr) -> int:
    """2 x output elements x window elements x input features — a
    coarse but monotone convolution count (none of the registry models
    convolve; kept for completeness)."""
    out = shape_elements(instr.shape)
    m = _WINDOW_SIZE_RE.search(instr.attrs)
    window = 1
    if m:
        for d in m.group(1).split("x"):
            if d.strip():
                window *= int(d)
    return 2 * out * window


@dataclasses.dataclass(frozen=True)
class ComputationCost:
    """Aggregate cost of one computation (loop multipliers applied to
    everything it transitively calls)."""

    name: str
    flops: int
    hbm_bytes: int
    wire_bytes: int
    # base collective opcode -> wire bytes attributed to it
    wire_by_collective: dict[str, int]
    # while-instruction names (qualified comp/instr) whose trip count
    # could not be derived: their bodies were counted ONCE.
    unknown_trip_whiles: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """Static per-step cost of one compiled module (per chip)."""

    flops: int
    hbm_bytes: int
    wire_bytes: int
    wire_by_collective: dict[str, int]
    unknown_trip_whiles: tuple[str, ...]
    num_partitions: int
    entry: ComputationCost

    @property
    def lower_bound(self) -> bool:
        """True when an unknown-trip-count while made this estimate a
        lower bound (loud, never silently dropped)."""
        return bool(self.unknown_trip_whiles)

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per HBM byte — the roofline x-axis."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0


def _merge_wire(into: dict[str, int], frm: dict[str, int], mult: int = 1):
    for k, v in frm.items():
        into[k] = into.get(k, 0) + v * mult


def estimate_cost(hlo_text: str) -> ProgramCost:
    """Walk a compiled module's scheduled HLO and price it (module doc)."""
    module = parse_module(hlo_text)
    default_n = 1
    m = _NUM_PARTITIONS_RE.search(module.header)
    if m:
        default_n = int(m.group(1))
    memo: dict[str, ComputationCost] = {}
    cost = _computation_cost(
        module.entry, module, memo, default_n, stack=frozenset()
    )
    return ProgramCost(
        flops=cost.flops,
        hbm_bytes=cost.hbm_bytes,
        wire_bytes=cost.wire_bytes,
        wire_by_collective=dict(cost.wire_by_collective),
        unknown_trip_whiles=cost.unknown_trip_whiles,
        num_partitions=default_n,
        entry=cost,
    )


def _callee(module: HloModule, name: str) -> HloComputation | None:
    return module.computations.get(name)


def _computation_cost(
    comp: HloComputation,
    module: HloModule,
    memo: dict[str, ComputationCost],
    default_n: int,
    stack: frozenset,
) -> ComputationCost:
    if comp.name in memo:
        return memo[comp.name]
    if comp.name in stack:  # defensive: HLO call graphs are acyclic
        return ComputationCost(comp.name, 0, 0, 0, {}, ())
    stack = stack | {comp.name}

    flops = 0
    hbm = 0
    wire = 0
    wire_by: dict[str, int] = {}
    unknown: list[str] = []

    def sub(name: str) -> ComputationCost:
        callee = _callee(module, name)
        if callee is None:
            return ComputationCost(name, 0, 0, 0, {}, ())
        return _computation_cost(callee, module, memo, default_n, stack)

    for instr in comp.instructions:
        op = instr.opcode
        if op in _FREE_OPCODES:
            continue
        operand_bytes = sum(
            shape_bytes(s) for s in instr.operand_shapes
        )
        boundary_bytes = operand_bytes + instr.bytes

        if op == "fusion" or op == "call":
            # Boundary counting: bytes at the fusion's operands/output
            # only; FLOPs (and any nested loops) from the body.
            inner = sub(instr.called[0]) if instr.called else None
            hbm += boundary_bytes
            if inner is not None:
                flops += inner.flops
                wire += inner.wire_bytes
                _merge_wire(wire_by, inner.wire_by_collective)
                unknown.extend(inner.unknown_trip_whiles)
            continue

        if op == "while":
            tm = _TRIP_COUNT_RE.search(instr.attrs)
            trips = int(tm.group(1)) if tm else None
            body = cond = None
            for nm in instr.called:
                role = module.roles.get(nm, "")
                if role == "body":
                    body = sub(nm)
                elif role == "condition":
                    cond = sub(nm)
            mult = trips if trips is not None else 1
            if trips is None:
                unknown.append(f"{comp.name}/{instr.name}")
            for part in (body, cond):
                if part is None:
                    continue
                flops += part.flops * mult
                hbm += part.hbm_bytes * mult
                wire += part.wire_bytes * mult
                _merge_wire(wire_by, part.wire_by_collective, mult)
                unknown.extend(part.unknown_trip_whiles)
            # The carry iterates in place; the while instruction itself
            # moves nothing beyond what the body already counted.
            continue

        if op == "conditional":
            # Upper bound: the most expensive branch, plus the
            # predicate/operand handoff once.
            branches = [sub(nm) for nm in instr.called]
            hbm += boundary_bytes
            if branches:
                worst = max(branches, key=lambda c: c.flops + c.hbm_bytes)
                flops += worst.flops
                hbm += worst.hbm_bytes
                wire += worst.wire_bytes
                _merge_wire(wire_by, worst.wire_by_collective)
                for b in branches:
                    unknown.extend(b.unknown_trip_whiles)
            continue

        if _is_collective(op):
            base = _collective_base(op)
            if base is not None:
                # Payload: the full tensor on the wire — the operand for
                # reduce-scatter (output is the 1/N shard), the output
                # for everything else (gathers inflate, reduces match).
                payload = (
                    operand_bytes if base == "reduce-scatter"
                    else instr.bytes
                )
                n = group_size(instr.attrs, default=default_n)
                w = collective_wire_bytes(base, payload, n)
                wire += w
                wire_by[base] = wire_by.get(base, 0) + w
                hbm += boundary_bytes
            continue

        hbm += boundary_bytes
        if op in _MOVE_OPCODES:
            continue
        if op == "dot":
            flops += _dot_flops(instr)
        elif op == "convolution":
            flops += _conv_flops(instr)
        elif op in _REDUCE_OPCODES:
            flops += max(
                [shape_elements(s) for s in instr.operand_shapes]
                or [shape_elements(instr.shape)]
            )
        else:
            flops += shape_elements(instr.shape)

    result = ComputationCost(
        name=comp.name,
        flops=flops,
        hbm_bytes=hbm,
        wire_bytes=wire,
        wire_by_collective=wire_by,
        unknown_trip_whiles=tuple(unknown),
    )
    memo[comp.name] = result
    return result


# ---------------------------------------------------------------------------
# Roofline projection
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RooflineSpec:
    """Chip constants the roofline prices a ProgramCost at.

    Public-spec assumptions, not measurements (same stance as
    ``profiling/comm_model.ChipSpec`` — v5e: 197 TFLOP/s bf16, ~819 GB/s
    HBM, conservative 45 GB/s per-chip effective collective
    throughput). Pass your own spec for another chip or a measured rig.
    """

    name: str
    peak_flops: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float


V5E_ROOFLINE = RooflineSpec(
    name="v5e",
    peak_flops=197e12,
    hbm_bytes_per_s=819e9,
    ici_bytes_per_s=45e9,
)


def project_step_time(
    cost: ProgramCost,
    spec: RooflineSpec = V5E_ROOFLINE,
    *,
    overlapped_comm: bool = False,
) -> dict:
    """Roofline step-time projection: max of the compute-bound and
    bandwidth-bound times, with the collective wire term either hidden
    under them (``overlapped_comm=True`` — the program carries an
    ``async_min_compute`` overlap contract) or fully exposed
    (serialised on top — no contract, no benefit of the doubt).

    Returns the projected seconds, the per-resource times, which
    resource binds, and the spec's ridge intensity (FLOP/byte at which
    compute and bandwidth bound times cross).
    """
    t_compute = cost.flops / spec.peak_flops
    t_hbm = cost.hbm_bytes / spec.hbm_bytes_per_s
    t_wire = cost.wire_bytes / spec.ici_bytes_per_s
    on_chip = max(t_compute, t_hbm)
    step = max(on_chip, t_wire) if overlapped_comm else on_chip + t_wire
    if t_wire > on_chip:
        bound = "wire"
    elif t_compute >= t_hbm:
        bound = "compute"
    else:
        bound = "bandwidth"
    return {
        "spec": spec.name,
        "projected_step_s": step,
        "compute_s": t_compute,
        "hbm_s": t_hbm,
        "wire_s": t_wire,
        "wire_overlapped": overlapped_comm,
        "bound": bound,
        "arithmetic_intensity": cost.arithmetic_intensity,
        "ridge_intensity": spec.peak_flops / spec.hbm_bytes_per_s,
        "lower_bound": cost.lower_bound,
    }


def projected_tok_s(
    cost: ProgramCost,
    tokens_per_step: int,
    spec: RooflineSpec = V5E_ROOFLINE,
    *,
    overlapped_comm: bool = False,
) -> float:
    """Tokens/s the roofline projects for a decode-step program that
    advances ``tokens_per_step`` tokens per dispatch (active rows x
    tokens-per-tick). Never calibrated against a chip run."""
    proj = project_step_time(cost, spec, overlapped_comm=overlapped_comm)
    step = proj["projected_step_s"]
    return tokens_per_step / step if step > 0 else 0.0
