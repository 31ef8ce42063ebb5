"""HTTP/SSE front-door battery (serving/server.py).

Exercises the wire tier end to end over a real socket: health probe,
blocking generate, an SSE stream that SURVIVES a mid-stream replica
kill (the README quickstart scenario, asserted bit-identical), the
per-request deadline mapping, 429 + Retry-After shedding, abort, and
the admin maintenance handles. Everything runs against a tiny model on
an ephemeral port inside one event loop per test — no web framework,
no fixed ports, no sleeps longer than the scheduler needs.
"""

import asyncio
import json

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.models import get_model
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
)
from pytorch_distributed_tpu.serving.router import ReplicaRouter
from pytorch_distributed_tpu.serving.server import ServingServer

pytestmark = pytest.mark.full


def _cfg():
    return ModelConfig(
        family="gpt2", vocab_size=97, n_ctx=64, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0,
    )


def _setup(cfg, params, *, n_replicas=2, clock=None, **router_kw):
    def make_engine(rep_id):
        kw = {}
        if clock is not None:
            kw = dict(clock=clock, sleep=clock.sleep)
        return BatchedDecodeEngine(
            cfg, slots=2, max_len=24, buckets=BucketSpec((8,)),
            retry_backoff_s=0.0, **kw,
        )

    if clock is not None:
        router_kw.setdefault("clock", clock)
    router = ReplicaRouter(make_engine, n_replicas, **router_kw)
    router.warmup(params)
    return ServingServer(router, params, default_max_new=4)


async def _http(host, port, method, path, body=None):
    """One request/response over a fresh connection. Returns
    (status, headers-dict, body-bytes)."""
    reader, writer = await asyncio.open_connection(host, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write(
        (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
         f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload
    )
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), 120)
    writer.close()
    head, _, rest = raw.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for line in lines[1:]:
        k, _, v = line.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, rest


def _sse_events(raw: bytes):
    """Parse an SSE body into [(event, data-dict)] ('message' default)."""
    out = []
    for block in raw.decode().split("\n\n"):
        event, data = "message", None
        for line in block.strip().split("\n"):
            if line.startswith("event:"):
                event = line[len("event:"):].strip()
            elif line.startswith("data:"):
                data = json.loads(line[len("data:"):].strip())
        if data is not None:
            out.append((event, data))
    return out


@pytest.mark.slow
def test_server_roundtrip_and_failover_stream():
    """healthz, blocking generate (greedy — tokens equal the engine
    reference), an SSE stream killed out from under mid-flight (admin
    kill; the stream completes bit-identically on the survivor), and
    admin restart."""
    cfg = _cfg()
    params = get_model(cfg).init(jax.random.key(0), cfg)

    # Engine reference for both requests (greedy => deterministic).
    ref_eng = BatchedDecodeEngine(
        cfg, slots=2, max_len=24, buckets=BucketSpec((8,))
    )
    r0 = ref_eng.submit(np.asarray([1, 2, 3], np.int32), 4)
    r1 = ref_eng.submit(np.asarray([5, 6, 7, 8], np.int32), 8)
    while ref_eng.has_work():
        ref_eng.step(params)
    ref_short = [int(t) for t in ref_eng.pop_result(r0).tokens]
    ref_long = [int(t) for t in ref_eng.pop_result(r1).tokens]

    server = _setup(cfg, params)

    async def scenario():
        host, port = await server.start()
        try:
            status, _, body = await _http(host, port, "GET", "/healthz")
            assert status == 200
            health = json.loads(body)
            assert set(health["replicas"]) == {"0", "1"}
            assert health["replicas"]["0"]["state"] == "HEALTHY"

            status, _, body = await _http(
                host, port, "POST", "/v1/generate",
                {"prompt": [1, 2, 3], "max_new_tokens": 4},
            )
            assert status == 200
            res = json.loads(body)
            assert res["state"] == "DONE" and res["tokens"] == ref_short

            # SSE stream + mid-stream kill of the replica serving it.
            reader, writer = await asyncio.open_connection(host, port)
            payload = json.dumps({
                "prompt": [5, 6, 7, 8], "max_new_tokens": 8,
                "stream": True,
            }).encode()
            writer.write(
                (f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                 f"Content-Length: {len(payload)}\r\n\r\n").encode()
                + payload
            )
            await writer.drain()
            buf = b""
            killed = False
            while True:
                chunk = await asyncio.wait_for(reader.read(4096), 60)
                if not chunk:
                    break
                buf += chunk
                if not killed and b"data:" in buf:
                    killed = True
                    s, _, kb = await _http(
                        host, port, "POST", "/admin/kill", {"replica": 0}
                    )
                    assert s == 200
                    assert json.loads(kb)["states"]["0"] == "DOWN"
            writer.close()
            events = _sse_events(buf)
            done = [d for e, d in events if e == "done"]
            assert len(done) == 1
            assert done[0]["state"] == "DONE"
            assert done[0]["tokens"] == ref_long  # bit-identical failover
            streamed = [d["token"] for e, d in events if e == "message"]
            assert streamed == ref_long[4:]  # every generated token, once

            status, _, body = await _http(
                host, port, "POST", "/admin/restart", {"replica": 0}
            )
            assert status == 200
            assert json.loads(body)["states"]["0"] == "HEALTHY"
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_server_shed_429_deadline_and_abort():
    """Overload maps to 429 + Retry-After; timeout_s maps onto the
    engine deadline (EXPIRED terminal over the wire); abort works and
    unknown rids 404; malformed bodies 400."""
    cfg = _cfg()
    params = get_model(cfg).init(jax.random.key(0), cfg)
    from pytorch_distributed_tpu.serving.chaos import VirtualClock

    # VirtualClock shared by engines + router: the deadline expires
    # exactly when the TEST advances time — no wall-clock racing.
    clock = VirtualClock()
    server = _setup(
        cfg, params, n_replicas=1, shed_queue_depth=1, clock=clock
    )

    async def scenario():
        host, port = await server.start()
        try:
            # Deadline: a 16-token request with a 40ms (virtual) budget.
            # Virtual time only moves when we advance it — do so once
            # the request is in flight; its next tick expires it
            # MID-DECODE and the wire reports EXPIRED with the clean
            # partial prefix.
            probe = asyncio.create_task(_http(
                host, port, "POST", "/v1/generate",
                {"prompt": [7, 7], "max_new_tokens": 16,
                 "timeout_s": 0.04},
            ))
            # Advance time only once the submit has landed (its deadline
            # is taken at submit; advancing first would push the
            # deadline past the advance and the request would finish
            # DONE).
            for _ in range(500):
                _, _, body = await _http(host, port, "GET", "/healthz")
                rep = json.loads(body)["replicas"]["0"]
                if rep["queue_depth"] + rep["active_rows"] >= 1:
                    break
                await asyncio.sleep(0.005)
            clock.advance(1.0)
            status, _, body = await probe
            assert status == 200
            res = json.loads(body)
            assert res["state"] == "EXPIRED"
            assert res["tokens"][:2] == [7, 7]  # clean partial prefix

            # Shed: a long blocker plus a concurrent burst overflows the
            # one-deep admission budget — at least one burst probe must
            # 429 with a Retry-After hint.
            blocker = asyncio.create_task(_http(
                host, port, "POST", "/v1/generate",
                {"prompt": [3] * 8, "max_new_tokens": 16},
            ))
            probes = await asyncio.gather(*[
                _http(host, port, "POST", "/v1/generate",
                      {"prompt": [4, 5], "max_new_tokens": 2})
                for _ in range(6)
            ])
            rejected = [
                (h, json.loads(b)) for s, h, b in probes if s == 429
            ]
            assert rejected, "overload never shed"
            headers, body = rejected[0]
            assert int(headers["retry-after"]) >= 1
            assert body["retry_after_s"] > 0
            await blocker

            # Abort + error paths.
            status, _, body = await _http(
                host, port, "POST", "/v1/abort", {"rid": 10_000}
            )
            assert status == 404
            status, _, _ = await _http(
                host, port, "POST", "/v1/generate", {"prompt": []}
            )
            assert status == 400
            status, _, _ = await _http(
                host, port, "POST", "/v1/generate",
                {"prompt": [1], "max_new_tokens": 10_000},
            )
            assert status == 400  # budget overflow rejects loudly
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_healthz_keeps_its_keys_and_counts_the_front_door():
    """``/healthz`` after a shed: every key it had before the ``server``
    group and the ``timers`` came is still there, and the front door's
    counters add up to the requests this test made."""
    cfg = _cfg()
    params = get_model(cfg).init(jax.random.key(0), cfg)
    server = _setup(cfg, params, n_replicas=1, shed_queue_depth=1)

    async def scenario():
        host, port = await server.start()
        try:
            blocker = asyncio.create_task(_http(
                host, port, "POST", "/v1/generate",
                {"prompt": [3] * 8, "max_new_tokens": 16, "stream": True},
            ))
            probes = await asyncio.gather(*[
                _http(host, port, "POST", "/v1/generate",
                      {"prompt": [4, 5], "max_new_tokens": 2})
                for _ in range(6)
            ])
            status, _, raw = await blocker
            assert status == 200
            assert len(_sse_events(raw)) == 16 + 1  # the tokens, then done
            bad, _, _ = await _http(
                host, port, "POST", "/v1/generate", {"prompt": []}
            )
            assert bad == 400
            _, _, body = await _http(host, port, "GET", "/healthz")
            return [s for s, _, _ in probes], json.loads(body)
        finally:
            await server.stop()

    statuses, health = asyncio.run(scenario())
    shed = statuses.count(429)
    assert shed >= 1 and statuses.count(200) == 6 - shed
    # today's keys, all of them
    assert {"replicas", "orphans", "undelivered_results", "sessions",
            "counters"} <= set(health)
    rep = health["replicas"]["0"]
    assert {
        "state", "tick_ema_s", "down_reason", "engine", "role",
        "device_ids", "queue_depth", "queue_depth_by_tier", "slots",
        "active_rows", "free_slots", "pool_pages", "free_pages",
        "pages_in_use", "session_pinned_pages", "sessions",
        "prefix_hit_rate", "kv_quant", "speculative_k", "spec_accept_rate",
        "counters",
    } <= set(rep)
    assert health["counters"]["shed"] == shed
    # the new groups
    counters = health["server"]["counters"]
    assert counters == {
        "http_requests": 9,  # blocker, 6 probes, the bad one, this probe
        "http_429": shed, "http_4xx": 1, "client_disconnects": 0,
        "streams_open": 0, "tokens_sent": 16,
    }
    timers = health["server"]["timers"]
    assert timers["request_wall"]["count"] == 7 - shed  # the 200s only
    assert timers["server.sse_write"]["count"] >= 1
    # 16 tokens: two in the tick that admits the request, one a tick after
    assert health["timers"]["router.tick"]["count"] >= 15
    assert health["timers"]["router.submit"]["count"] == 7  # shed ones too
    done = rep["counters"]["done"]
    assert rep["timers"]["queue_wait"]["count"] == done == 7 - shed
    assert rep["timers"]["engine.tick"]["count"] >= 15


def test_open_streams_take_no_lock_of_their_own_between_ticks():
    """The drive loop reads every open stream's progress under the tick's
    own hold of the router lock (``_tick``): however many streams are open,
    a tick is ONE locked call, and a stream adds its submit and the pop of
    its result. (Each poller took the lock twice a tick: at 64 streams the
    next tick queued 21 ms behind them with the chip idle.) Every stream
    still gets every token and its ``done`` event."""
    cfg = _cfg()
    params = get_model(cfg).init(jax.random.key(0), cfg)
    server = _setup(cfg, params, n_replicas=1)
    calls = []
    locked = server._locked
    server._locked = lambda fn, *a, **kw: (
        calls.append(getattr(fn, "__name__", "?")), locked(fn, *a, **kw))[1]

    async def scenario():
        host, port = await server.start()
        try:
            return await asyncio.gather(*[
                _http(host, port, "POST", "/v1/generate",
                      {"prompt": [3 + i] * 6, "max_new_tokens": 12,
                       "stream": True})
                for i in range(2)
            ])
        finally:
            await server.stop()

    for status, _, raw in asyncio.run(scenario()):
        events = _sse_events(raw)
        assert status == 200 and len(events) == 12 + 1
        assert events[-1][0] == "done"
    ticks = server.router.timers.snapshot()["router.tick"]["count"]
    assert ticks >= 11
    # what the two streams asked for themselves; a slow tick (its compile,
    # a loaded host) may add a stream's own read after 0.25 s without one
    assert calls.count("submit") == 2 and calls.count("pop_result") == 2
    assert calls.count("_peek") < ticks / 2  # (it was four a tick)
    assert calls.count("progress") == 0  # never on its own: inside _peek
    # every other call is the drive loop's: one a tick, some that found
    # no work
    assert calls.count("_tick") >= ticks
    assert set(calls) <= {"submit", "pop_result", "_peek", "_tick"}
