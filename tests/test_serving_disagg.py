"""Disaggregated prefill/decode serving: KV page handoff between replicas.

What this file pins (PR 20):

1. handoff bit-equality — a disaggregated fleet (PREFILL worker exports
   finished rows, DECODE worker imports and finishes them) produces
   DONE tokens bit-identical to one colocated engine, with ZERO
   steady-state compiles on both workers. The fast plain case rides
   tier-1; the int8/TP matrix rides the slow tier.
2. the role routing pins, both directions — fresh prompts never route
   to DECODE workers (``_admissible``), and decode work (handoffs,
   failover re-adoption) never routes to PREFILL workers
   (``_handoff_target`` / ``_least_loaded``).
3. mid-handoff fault injection, both directions — prefill death parks
   its un-handed-off rows (the decode survivor cannot re-prefill),
   restart resumes bit-equal; decode death hands its rows back through
   ordinary failover re-adoption on the prefill side, bit-equal.
4. role-reassignment churn — restarting replicas under NEW roles pays
   its compile set once at restart warmup and adds zero steady
   compiles after.
5. the ``kv_handoff``/``role_assign`` JSONL event schema
   (docs/ROBUSTNESS.md §5) and the uniform ``stats()`` role/device_ids
   fields the router's scoring reads.
6. placement plumbing — engine ``device=`` pinning shows up in
   ``stats()["device_ids"]``; ``MeshConfig.device_ids`` validates; the
   two knobs are mutually exclusive.
7. ``disagg_stream`` determinism — request i's content derives from
   (seed, i) alone, so colocated and disaggregated legs replay
   request-for-request identical traffic.
"""

import logging

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.models import get_model
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    DecodeEngine,
    PagedBatchedDecodeEngine,
)
from pytorch_distributed_tpu.serving.lifecycle import RouterOverloaded
from pytorch_distributed_tpu.serving.router import ReplicaRouter
from pytorch_distributed_tpu.serving.workload import disagg_stream

pytestmark = pytest.mark.full


def _cfg(**kw):
    return ModelConfig(
        family="gpt2", vocab_size=97, n_ctx=64, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0, **kw,
    )


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.key(seed), cfg)


PAGED_KW = dict(slots=3, max_len=32, page_size=8, prefill_chunk=8)


def _reqs(n=6, seed=7):
    rng = np.random.default_rng(seed)
    shapes = [(11, 6), (4, 9), (17, 5), (7, 7), (13, 8), (5, 10)][:n]
    return [
        dict(
            prompt=rng.integers(1, 97, size=tp).astype(np.int32),
            max_new_tokens=mn, temperature=0.8,
            key=jax.random.key(100 + i),
        )
        for i, (tp, mn) in enumerate(shapes)
    ]


def _reference(cfg, params, reqs, **engine_kw):
    """One colocated paged engine, same requests: DONE tokens depend
    only on (request, params) — the schedule-independence every
    disaggregation assertion leans on."""
    kw = dict(PAGED_KW, **engine_kw)
    eng = PagedBatchedDecodeEngine(cfg, **kw)
    rids = [eng.submit(**r) for r in reqs]
    eng.run(params)
    return [list(np.asarray(eng.pop_result(r).tokens)) for r in rids]


def _disagg_factory(cfg, *, pin_devices=True, **engine_kw):
    """Replica 0 = PREFILL worker, replica 1 = DECODE worker, each on
    its own device when pinned."""
    kw = dict(PAGED_KW, **engine_kw)

    def make_engine(rep_id):
        return PagedBatchedDecodeEngine(
            cfg, role="prefill" if rep_id == 0 else "decode",
            device=jax.devices()[rep_id] if pin_devices else None,
            **kw,
        )

    return make_engine


class _EventTap(logging.Handler):
    """Capture serving JSONL events (``event=<name> k=v ...``) without
    flooding stdout through the root pdtpu StreamHandler."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.events = []

    def emit(self, record):
        msg = record.getMessage()
        if not msg.startswith("event="):
            return
        fields = dict(p.split("=", 1) for p in msg.split(" "))
        self.events.append({"event": fields.pop("event"), **fields})

    def __enter__(self):
        self._lg = logging.getLogger("pdtpu.serving")
        self._level = self._lg.level
        self._propagate = self._lg.propagate
        self._lg.addHandler(self)
        self._lg.setLevel(logging.DEBUG)
        self._lg.propagate = False
        return self

    def __exit__(self, *exc):
        self._lg.removeHandler(self)
        self._lg.setLevel(self._level)
        self._lg.propagate = self._propagate
        return False


# -- the disaggregation workload generator ---------------------------------


def test_disagg_stream_deterministic_and_index_independent():
    """Request i's content folds from (seed, i) ALONE: same seed ->
    bitwise-same stream, and truncating/extending the stream never
    perturbs earlier requests."""
    a = disagg_stream(3, n=12, vocab_size=97)
    b = disagg_stream(3, n=12, vocab_size=97)
    short = disagg_stream(3, n=5, vocab_size=97)
    for i, (ra, rb) in enumerate(zip(a, b)):
        assert sorted(ra) == sorted(rb)
        assert np.array_equal(ra["prompt"], rb["prompt"])
        assert ra["max_new_tokens"] == rb["max_new_tokens"]
        assert ra["kind"] == rb["kind"]
        if "key" in ra:
            assert np.array_equal(
                jax.random.key_data(ra["key"]),
                jax.random.key_data(rb["key"]),
            )
        if i < len(short):
            assert np.array_equal(ra["prompt"], short[i]["prompt"])
    # Both interference classes present, shaped as advertised.
    kinds = {r["kind"] for r in a}
    assert kinds == {"heavy_prefill", "light"}
    for r in a:
        if r["kind"] == "heavy_prefill":
            assert len(r["prompt"]) >= 96 and r["max_new_tokens"] <= 8
        else:
            assert len(r["prompt"]) <= 24 and r["max_new_tokens"] >= 24
    assert disagg_stream(4, n=12, vocab_size=97) != a


# -- uniform stats(): role + device_ids ------------------------------------


def test_stats_role_and_device_ids_uniform():
    """Every engine reports ``role`` and ``device_ids`` — the router's
    role pins read them without hasattr probing. ``device=`` pinning shows up as the pinned id."""
    cfg = _cfg()
    serial = DecodeEngine(cfg, max_len=24)
    dense = BatchedDecodeEngine(
        cfg, slots=2, max_len=24, buckets=BucketSpec((8,))
    )
    pinned_dev = jax.devices()[3]
    paged = PagedBatchedDecodeEngine(cfg, device=pinned_dev, **PAGED_KW)
    for eng in (serial, dense, paged):
        st = eng.stats()
        assert st["role"] in ("colocated", "prefill", "decode")
        assert isinstance(st["device_ids"], list)
    assert paged.stats()["device_ids"] == [pinned_dev.id]
    assert paged.stats()["role"] == "colocated"
    assert PagedBatchedDecodeEngine(
        cfg, role="prefill", **PAGED_KW
    ).stats()["role"] == "prefill"
    with pytest.raises(ValueError, match="role"):
        PagedBatchedDecodeEngine(cfg, role="bogus", **PAGED_KW)


def test_placement_knobs_validate():
    """MeshConfig.device_ids validates (unique, mesh-sized); a meshed
    engine refuses the single-chip ``device=`` knob — placement goes
    through exactly one door."""
    with pytest.raises(ValueError, match="unique"):
        MeshConfig(tensor=2, strategy="no_shard", device_ids=(1, 1))
    with pytest.raises(ValueError, match="device_ids"):
        MeshConfig(tensor=2, strategy="no_shard", device_ids=(0, 1, 2))
    cfg = _cfg()
    with pytest.raises(ValueError, match="MeshConfig.device_ids"):
        PagedBatchedDecodeEngine(
            cfg, mesh_cfg=MeshConfig(tensor=2, strategy="no_shard"),
            device=jax.devices()[0], **PAGED_KW,
        )


# -- role gates, both directions -------------------------------------------


def test_role_gates_on_the_engine():
    cfg = _cfg()
    dec = PagedBatchedDecodeEngine(cfg, role="decode", **PAGED_KW)
    with pytest.raises(ValueError, match="DECODE worker"):
        dec.submit(np.arange(1, 5, dtype=np.int32), 3)
    pre = PagedBatchedDecodeEngine(cfg, role="prefill", **PAGED_KW)
    with pytest.raises(ValueError, match="PREFILL worker"):
        pre.import_handoff(None)  # role gate fires before field access
    # Geometry mismatches refuse loudly rather than corrupting pools.
    cfg2 = _cfg()
    params = _params(cfg2)
    pre2 = PagedBatchedDecodeEngine(cfg2, role="prefill", **PAGED_KW)
    rid = pre2.submit(np.arange(1, 10, dtype=np.int32), 3)
    while not pre2.handoff_ready():
        pre2.step(params)
    h = pre2.export_handoff(rid)
    other = PagedBatchedDecodeEngine(
        cfg2, role="decode", slots=3, max_len=32, page_size=16,
        prefill_chunk=16,
    )
    with pytest.raises(ValueError, match="geometry"):
        other.import_handoff(h)
    assert not other.can_import_handoff(h)


def test_router_role_pins_both_directions():
    """Fresh prompts never land on the DECODE worker; handoffs and
    failover re-adoption never land on the PREFILL worker — pinned at
    the router scoring level (``_admissible`` / ``_least_loaded`` /
    ``_handoff_target``), not just observed end-to-end."""
    cfg = _cfg()
    params = _params(cfg)
    router = ReplicaRouter(_disagg_factory(cfg, pin_devices=False), 2)
    router.warmup(params)
    pre, dec = router._replicas
    # decode-ward: a completely idle DECODE worker is inadmissible.
    assert router._admissible(dec) is None
    assert router._admissible(pre) is not None
    # failover mirror: re-adoption (re-PREFILL work) skips decode too.
    assert router._least_loaded() is pre
    # sessions need a replica that both prefills AND decodes.
    assert router._least_loaded(colocated_only=True) is None
    with pytest.raises(RuntimeError, match="colocated"):
        router.open_session()
    # prefill-ward: the handoff pump's target scoring skips the
    # prefill worker even though its engine could physically import.
    rid = router.submit(**_reqs(1)[0])
    while not pre.engine.handoff_ready():
        pre.engine.step(params)
    h = pre.engine.export_handoff(pre.rid_map and next(iter(
        erid for erid in [s.rid for s in pre.engine._slots if s]
    )))
    assert router._handoff_target(h) is dec
    router.run(params)
    assert router.pop_result(rid).state == "DONE"
    # End-to-end shape: every prompt prefilled on 0, decoded on 1.
    assert pre.engine.counters["handoffs_out"] == 1
    assert dec.engine.counters["handoffs_in"] == 1
    # All-decode fleet: nothing is admissible at all.
    lonely = ReplicaRouter(
        lambda i: PagedBatchedDecodeEngine(
            cfg, role="decode", **PAGED_KW
        ),
        1,
    )
    with pytest.raises(RouterOverloaded):
        lonely.submit(np.arange(1, 5, dtype=np.int32), 3)


# -- handoff bit-equality ---------------------------------------------------


def _run_disagg(cfg, params, reqs, *, events=False, **engine_kw):
    router = ReplicaRouter(_disagg_factory(cfg, **engine_kw), 2)
    router.warmup(params)
    tap = _EventTap()
    with tap:
        rids = [router.submit(**r) for r in reqs]
        router.run(params)
    toks = [list(np.asarray(router.pop_result(r).tokens)) for r in rids]
    return (router, toks, tap.events) if events else (router, toks)


def test_handoff_bit_equality_plain():
    """The fast tier-1 case: disagg fleet == colocated engine, token
    for token, with zero steady compiles and one handoff per request —
    and the kv_handoff JSONL events carry the pinned schema."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    ref = _reference(cfg, params, reqs)
    router, got, events = _run_disagg(
        cfg, params, reqs, events=True, pin_devices=True
    )
    assert got == ref
    assert all(v == 0 for v in router.steady_compiles().values())
    assert router.counters["handoffs"] == len(reqs)
    st = router.stats()["replicas"]
    assert st[0]["role"] == "prefill" and st[1]["role"] == "decode"
    assert st[0]["device_ids"] == [jax.devices()[0].id]
    assert st[1]["device_ids"] == [jax.devices()[1].id]
    # JSONL schema (docs/ROBUSTNESS.md §5): rid + endpoints + bytes +
    # latency on every kv_handoff; role_assign logged per replica.
    hand = [e for e in events if e["event"] == "kv_handoff"]
    assert len(hand) == len(reqs)
    for e in hand:
        for k in ("rid", "from_replica", "to_replica", "pages",
                  "bytes", "useful_bytes", "export_s", "latency_s", "t"):
            assert k in e, f"kv_handoff event missing {k}"
        assert int(e["from_replica"]) == 0
        assert int(e["to_replica"]) == 1
        assert int(e["bytes"]) >= int(e["useful_bytes"]) > 0


@pytest.mark.slow
@pytest.mark.parametrize("variant", ["int8", "tp"])
def test_handoff_bit_equality_matrix(variant):
    """The composition matrix: int8 KV pages (scale leaves ship with
    the pages) and tensor=2 fleets (each replica on its OWN device
    pair via MeshConfig.device_ids; each shard ships its own head
    slice) hand off bit-identically too."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    if variant == "int8":
        ref = _reference(cfg, params, reqs, kv_quant="int8")
        router, got = _run_disagg(
            cfg, params, reqs, pin_devices=True, kv_quant="int8"
        )
    else:
        mesh = MeshConfig(tensor=2, strategy="no_shard")
        ref = _reference(cfg, params, reqs, mesh_cfg=mesh)

        def make_engine(rep_id):
            return PagedBatchedDecodeEngine(
                cfg, role="prefill" if rep_id == 0 else "decode",
                mesh_cfg=MeshConfig(
                    tensor=2, strategy="no_shard",
                    device_ids=(0, 1) if rep_id == 0 else (2, 3),
                ),
                **PAGED_KW,
            )

        router = ReplicaRouter(make_engine, 2)
        router.warmup(params)
        rids = [router.submit(**r) for r in reqs]
        router.run(params)
        got = [
            list(np.asarray(router.pop_result(r).tokens)) for r in rids
        ]
    assert got == ref
    assert all(v == 0 for v in router.steady_compiles().values())
    assert router.counters["handoffs"] == len(reqs)


# -- mid-handoff fault injection, both directions ---------------------------


def test_prefill_death_mid_handoff():
    """The PREFILL worker dies with rows queued/parked: the decode
    survivor cannot re-prefill them (role pin), so they park as
    orphans; the restarted prefill worker re-adopts and the stream
    finishes bit-equal with zero steady compiles."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    ref = _reference(cfg, params, reqs)
    router = ReplicaRouter(_disagg_factory(cfg, pin_devices=True), 2)
    router.warmup(params)
    rids = [router.submit(**r) for r in reqs]
    router.step(params)  # prefill chunks in flight
    router.kill(0, reason="chaos: prefill death mid-handoff")
    assert router.stats()["orphans"] > 0  # decode can't adopt them
    router.restart(0, params)
    router.run(params)
    got = [list(np.asarray(router.pop_result(r).tokens)) for r in rids]
    assert got == ref
    assert all(v == 0 for v in router.steady_compiles().values())


def test_decode_death_failover():
    """The DECODE worker dies holding imported rows: they come back as
    resume entries, re-adopted by the prefill worker (re-PREFILL
    work), re-exported once the restarted decode worker is up —
    bit-equal, zero steady compiles."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    ref = _reference(cfg, params, reqs)
    router = ReplicaRouter(_disagg_factory(cfg, pin_devices=True), 2)
    router.warmup(params)
    rids = [router.submit(**r) for r in reqs]
    for _ in range(60):
        router.step(params)
        if router.stats()["replicas"][1]["active_rows"]:
            break
    else:
        pytest.fail("decode worker never received a handoff")
    router.kill(1, reason="chaos: decode death with imported rows")
    router.restart(1, params)
    router.run(params)
    got = [list(np.asarray(router.pop_result(r).tokens)) for r in rids]
    assert got == ref
    assert all(v == 0 for v in router.steady_compiles().values())


# -- role reassignment churn ------------------------------------------------


def test_role_reassignment_churn_zero_compiles():
    """Flipping a fleet from colocated/colocated to prefill/decode via
    kill+restart pays each new role's compile set ONCE at restart
    warmup (the steady watermark resets there) and adds nothing in
    steady state — role reassignment is an operational event, not a
    recompile storm."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    ref = _reference(cfg, params, reqs)
    roles = {0: "colocated", 1: "colocated"}

    def make_engine(rep_id):
        return PagedBatchedDecodeEngine(
            cfg, role=roles[rep_id], device=jax.devices()[rep_id],
            **PAGED_KW,
        )

    router = ReplicaRouter(make_engine, 2)
    router.warmup(params)
    rids = [router.submit(**r) for r in reqs]
    router.run(params)
    got = [list(np.asarray(router.pop_result(r).tokens)) for r in rids]
    assert got == ref
    assert router.counters["handoffs"] == 0  # colocated: none needed
    assert all(v == 0 for v in router.steady_compiles().values())
    # Reassign: 0 -> prefill, 1 -> decode.
    roles.update({0: "prefill", 1: "decode"})
    router.kill(0, reason="role reassignment")
    router.restart(0, params)
    router.kill(1, reason="role reassignment")
    router.restart(1, params)
    assert [
        router.stats()["replicas"][i]["role"] for i in (0, 1)
    ] == ["prefill", "decode"]
    rids = [router.submit(**r) for r in reqs]
    router.run(params)
    got = [list(np.asarray(router.pop_result(r).tokens)) for r in rids]
    assert got == ref
    assert router.counters["handoffs"] == len(reqs)
    assert all(v == 0 for v in router.steady_compiles().values())
