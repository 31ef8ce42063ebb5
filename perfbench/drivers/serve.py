"""The serving driver: ``ServingServer`` over ``ReplicaRouter`` over one
``PagedBatchedDecodeEngine``, built as ``scripts/serve.py`` builds them, and
an asyncio load generator speaking HTTP/SSE to it over loopback — in ONE
process, because one process holds the chip.

From the program: the three classes and ``/healthz``. The request mix, the
clocks, the percentiles and the comparison are the benchmark's. Every
request streams; each token event is stamped on the client's clock when the
client parses it.
"""

from __future__ import annotations

import asyncio
import functools
import gc
import json
import time
from pathlib import Path

import numpy as np

from perfbench import compare, flops, preset, reference, stats, trace, traffic

DRAIN_S = 90.0  # how long an answer may come after the window has closed
CLOSE_GRACE_S = 1.0  # a closed loop keeps sending this long past the nominal
# close, so that the token event that closes the window (see ``window_of``)
# still sees the cell's load


class Record:
    """What the client saw of one request."""

    __slots__ = ("req", "due", "sent", "status", "tokens", "times", "done",
                 "error")

    def __init__(self, req):
        self.req = req
        self.due = self.sent = None
        self.status = None
        self.tokens: list[int] = []
        self.times: list[float] = []
        self.done = None
        self.error = None

    @property
    def ok(self) -> bool:
        return (
            self.error is None and self.done is not None
            and self.done.get("state") == "DONE"
            and len(self.tokens) == self.req["body"]["max_new_tokens"])


async def post_stream(host: str, port: int, rec: Record) -> None:
    """POST /v1/generate with ``stream: true``; stamp every token event."""
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError as err:
        rec.error = f"connect: {err}"
        return
    try:
        payload = json.dumps(rec.req["body"]).encode()
        writer.write(
            (f"POST /v1/generate HTTP/1.1\r\nHost: perfbench\r\n"
             f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        rec.status = int(head.split()[1])
        if rec.status != 200:
            rec.error = f"HTTP {rec.status}: {(await reader.read())[:200]!r}"
            return
        buf = b""
        while True:
            chunk = await reader.read(1 << 16)
            if not chunk:
                break
            now = time.perf_counter()
            buf += chunk
            while b"\n\n" in buf:
                block, buf = buf.split(b"\n\n", 1)
                event, data = "message", None
                for line in block.split(b"\n"):
                    if line.startswith(b"event:"):
                        event = line[6:].strip().decode()
                    elif line.startswith(b"data:"):
                        data = json.loads(line[5:])
                if data is None:
                    continue
                if event == "done":
                    rec.done = data
                else:
                    rec.tokens.append(int(data["token"]))
                    rec.times.append(now)
        if rec.done is None:
            rec.error = "stream ended without its done event"
    except (OSError, asyncio.IncompleteReadError, ValueError) as err:
        rec.error = f"{type(err).__name__}: {err}"
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except OSError:
            pass


async def healthz(host: str, port: int) -> dict:
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(b"GET /healthz HTTP/1.1\r\nHost: perfbench\r\n\r\n")
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    return json.loads(raw.partition(b"\r\n\r\n")[2])


async def open_loop(host, port, reqs, t_start, records):
    async def one(req):
        rec = Record(req)
        records.append(rec)
        rec.due = t_start + req["due_s"]
        delay = rec.due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        rec.sent = time.perf_counter()
        await post_stream(host, port, rec)

    return [asyncio.create_task(one(r)) for r in reqs]


async def closed_loop(host, port, reqs, t_close, n_clients, records):
    queue = list(reversed(reqs))

    async def client():
        while queue and time.perf_counter() < t_close + CLOSE_GRACE_S:
            rec = Record(queue.pop())
            records.append(rec)
            rec.due = rec.sent = time.perf_counter()
            await post_stream(host, port, rec)

    return [asyncio.create_task(client()) for _ in range(n_clients)]


def build(ctx, params):
    """(router, server) as scripts/serve.py builds them; one replica."""
    import jax

    from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine
    from pytorch_distributed_tpu.serving.router import ReplicaRouter
    from pytorch_distributed_tpu.serving.server import ServingServer

    eng = ctx.traffic["engine"]
    cfg = preset.of(ctx.config, "serve").replace(n_ctx=max(eng["max_len"], 64))

    def make_engine(rep_id: int):
        # the mix's ``engine`` group is the engine's own keyword arguments
        return PagedBatchedDecodeEngine(cfg, device=jax.devices()[0], **eng)

    router = ReplicaRouter(make_engine, 1)
    ctx.mark("engine_built")
    n_programs = router.warmup(params)
    ctx.mark("programs_warmed")
    server = ServingServer(router, params, port=0)
    return cfg, router, server, n_programs


async def drive(ctx, server, router, reqs, warm_reqs):
    mix = ctx.traffic
    host, port = await server.start()
    out = {}
    try:
        warm = [Record(r) for r in warm_reqs]
        await asyncio.gather(*(post_stream(host, port, r) for r in warm))
        bad = [r.error or r.done for r in warm if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:2]}")
        out["compiles_before"] = sum(router.steady_compiles().values())
        ctx.mark("warm_requests_done")

        # The ramp fills the server at the cell's own load; it is set-up, and
        # the window opens on a server already in its steady state.
        records: list[Record] = []
        t_start = time.perf_counter() + mix["ramp_s"]
        t_close = t_start + ctx.seconds
        if mix["loop"] == "open":
            tasks = await open_loop(host, port, reqs, t_start, records)
        else:
            tasks = await closed_loop(
                host, port, reqs, t_close, mix["clients"], records)
        await asyncio.sleep(max(0.0, t_start - time.perf_counter()))
        out["setup_s"] = t_start - ctx.t0
        # /healthz whole, at both ends of the nominal window: a reader takes
        # any counter or timer the program serves, and its difference
        out["health"] = {"open": await healthz(host, port)}
        cap = None
        if ctx.trace:
            lead = min(ctx.seconds, mix["trace_seconds"])
            await asyncio.sleep(max(0.0, t_close - lead - time.perf_counter()))
            cap = trace.capture(str(Path(ctx.scratch) / "trace")).start()
            win_span = trace.span("window")
            win_span.__enter__()
        await asyncio.sleep(max(0.0, t_close - time.perf_counter()))
        out["health"]["close"] = await healthz(host, port)
        # the token event that closes the window comes within a burst or two
        # of the nominal close; stopping the profiler blocks this loop for
        # seconds, so it waits until that event has been stamped
        await asyncio.sleep(
            max(0.0, t_close + CLOSE_GRACE_S - time.perf_counter()))
        if cap is not None:
            win_span.__exit__(None, None, None)
            out["trace_path"] = cap.stop()
            out["cap"] = cap
        # Every request sent is waited for (a closed loop's clients send no
        # more after the close): the server cannot be stopped with a request
        # in flight, and an answer that comes late is late, not wrong.
        _, late = await asyncio.wait(tasks, timeout=DRAIN_S)
        for t in late:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for r in records:
            if r.error is None and r.done is None:
                r.error = f"unanswered {DRAIN_S:.0f} s after the close"
        out.update(records=records, t_start=t_start, t_close=t_close,
                   compiles_after=sum(router.steady_compiles().values()))
    finally:
        # stop() waits for every handler, and a handler whose client has gone
        # never ends: after an unanswered request (a failed run already) give
        # it up rather than hang; the loop's shutdown cancels what is left
        try:
            await asyncio.wait_for(server.stop(), 15.0)
        except asyncio.TimeoutError:
            pass
    return out


def logit_gaps(ctx, sample, precision_control=None) -> dict:
    """What the served tokens read against the reference, over the sampled
    requests. Greedy requests: the widest gap by which a served token's logit
    lies under the reference's best at its position (``served_logit_gap``).
    Requests sampled with ``top_k``: the widest gap by which a served token's
    logit lies under the reference's k-th best, nought where it is among the
    reference's top k (``sampled_topk_gap``): whatever the sampler drew, it
    may only have drawn from there. With ``precision_control`` also the same
    two gaps for the tokens the lower precision would serve at each of the
    same positions: the one it puts first, and the last one it would admit
    (its k-th), under ``control_*``."""
    import jax
    import jax.numpy as jnp

    model = ctx.config["model"]
    max_len = ctx.traffic["engine"]["max_len"]
    ref = reference.of(ctx.config)
    params = ref.init_params(
        ctx.seed, model, ctx.config["program"]["serve_overrides"]["param_dtype"])

    @functools.partial(jax.jit, static_argnames=("k",))
    def rows(params, ids, first, served, k):
        """Per position: how far the served token's logit lies under the
        reference's k-th best (k=1: its best), and the same for the token
        the control ranks k-th."""
        lg = ref.logits_at(params, ids, first, served.shape[0], model, "f32")
        kth = jax.lax.top_k(lg, k)[0][:, -1]

        def under(tokens):
            mine = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
            return jnp.maximum(kth - mine, 0.0)

        if not precision_control:
            return under(served), None
        lo = ref.logits_at(params, ids, first, served.shape[0], model,
                           precision_control)
        return under(served), under(jax.lax.top_k(lo, k)[1][:, -1])

    new_max = ctx.traffic["new_tokens"]["max"]
    names = {True: "served_logit_gap", False: "sampled_topk_gap"}
    out = {"tokens_compared": 0, "sampled_tokens_compared": 0}
    for rec in sample:
        prompt, served = rec.req["body"]["prompt"], rec.tokens
        ids = np.zeros((1, max_len), np.int32)
        seq = (prompt + served)[:max_len]
        ids[0, :len(seq)] = seq
        tok = np.zeros((new_max,), np.int32)
        tok[:len(served)] = served
        first = len(prompt) - 1  # the position whose logits chose served[0]
        k = 1 if rec.req["greedy"] else int(
            rec.req["body"].get("top_k") or model["vocab_size"])
        gaps, gaps_c = rows(params, ids, first, tok, k)
        name = names[rec.req["greedy"]]
        out[name] = max(out.get(name, 0.0),
                        float(np.asarray(gaps)[:len(served)].max()))
        out["tokens_compared" if rec.req["greedy"]
            else "sampled_tokens_compared"] += len(served)
        if precision_control:
            out["control_" + name] = max(
                out.get("control_" + name, 0.0),
                float(np.asarray(gaps_c)[:len(served)].max()))
    return out


def window_of(records, t_start: float, seconds: float):
    """The measured window, from token event to token event: it opens at the
    first token event at or after the ramp's end and closes at the first one
    ``seconds`` or more after that. The server hands tokens out in bursts,
    every row's at once every second tick (0.4 s); a window cut at fixed
    instants holds one burst more or fewer as the bursts happen to fall (1.1%
    of a 30 s window), one cut at events holds a whole number of them."""
    events = sorted(t for r in records for t in r.times)
    opened = [t for t in events if t >= t_start]
    closed = [t for t in opened if t >= opened[0] + seconds]
    if not closed:  # (so none opened it either, or none came after)
        return t_start, t_start + seconds  # nothing came: the nominal one
    return opened[0], closed[0]


def sample_of(records, seed: int, n_greedy: int, n_sampled: int):
    """The finished requests that go through the reference: of each kind the
    longest and a draw from the seed."""
    out = []
    for greedy, n, stream in ((True, n_greedy, 7), (False, n_sampled, 8)):
        done = sorted((r for r in records if r.ok and r.req["greedy"] == greedy),
                      key=lambda r: -len(r.tokens))
        if not done or n < 1:
            continue
        rng = np.random.default_rng([seed & 0xFFFFFFFF, stream])
        rest = rng.choice(np.arange(1, len(done)),
                          size=min(n - 1, len(done) - 1), replace=False)
        out += done[:1] + [done[i] for i in sorted(rest)]
    return out


def run(ctx) -> dict:
    import jax

    mix, model = ctx.traffic, ctx.config["model"]
    eng = mix["engine"]
    count = flops.of(ctx.config)
    pdt = ctx.config["program"]["serve_overrides"]["param_dtype"]
    params = reference.of(ctx.config).init_params(ctx.seed, model, pdt)
    cfg, router, server, n_programs = build(ctx, params)
    reqs = traffic.requests(
        mix, ctx.seed, ctx.seconds, model["vocab_size"], eng["max_len"])
    # The warm-up requests pass every program once through the front door:
    # the mix's own prompt lengths, replies cut to a few tokens.
    warm_reqs = traffic.requests(
        dict(mix, loop="open", cycle_requests=mix["warm_requests"],
             cycle_s=1.0, ramp_s=0.0), ctx.seed + 1,
        1.0, model["vocab_size"], eng["max_len"])
    for r in warm_reqs:
        r["body"]["max_new_tokens"] = min(
            r["body"]["max_new_tokens"], mix["warm_new_tokens"])
    got = asyncio.run(drive(ctx, server, router, reqs, warm_reqs))

    memory_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    form = None
    if "trace_path" in got:
        form = trace.load_xplane(got["trace_path"])
        got["cap"].discard()
    del params, router, server
    gc.collect()

    records, t_start, t_close = got["records"], got["t_start"], got["t_close"]
    failed = [r for r in records if not r.ok]
    due_in = [r for r in records if r.due >= t_start]  # the ramp's are set-up
    worst_ms = (ctx.seconds + DRAIN_S) * 1e3  # unanswered: the worst there is
    ttft = [(r.times[0] - r.due) * 1e3 if r.times else worst_ms for r in due_in]
    t_open, t_end = window_of(records, t_start, ctx.seconds)
    inside = lambda t: t_open < t <= t_end  # noqa: E731
    itl = [(b - a) * 1e3 for r in records
           for a, b in zip(r.times, r.times[1:]) if inside(b)]
    late = [(r.sent - r.due) * 1e3 for r in due_in if r.sent is not None]
    in_window = sum(1 for r in records for t in r.times if inside(t))
    at_fixed_cuts = sum(1 for r in records for t in r.times
                        if t_start <= t <= t_close)
    work = 0.0  # FLOPs the tokens processed inside the window needed
    for r in records:
        p = len(r.req["body"]["prompt"])
        if r.times and inside(r.times[0]):
            work += count.serve_flops_span(model, 0, p)
        n_dec = sum(1 for t in r.times[1:] if inside(t))
        work += count.serve_flops_span(model, p, p + n_dec)

    def in_flight(t):
        return sum(1 for r in records if r.sent is not None and r.sent <= t
                   and not (r.ok and r.times[-1] <= t))

    # -- the comparison: requests the run finished, of either kind ----------
    sample = sample_of(records, ctx.seed, mix["compare_requests"],
                       mix["compare_sampled_requests"])
    t_ref = time.perf_counter()
    gaps = logit_gaps(ctx, sample)
    reference_s = time.perf_counter() - t_ref
    wanted = ["served_logit_gap"] + (
        ["sampled_topk_gap"] if mix["sampled_share"] > 0
        and mix["compare_sampled_requests"] > 0 else [])
    numbers = compare.serving(
        {k: gaps.get(k, float("inf")) for k in wanted}, ctx.limits)
    numbers["compiles_in_window"] = {
        "value": got["compiles_after"] - got["compiles_before"], "limit": 0}
    numbers["unanswered_or_wrong_length"] = {"value": len(failed), "limit": 0}

    health = got["health"]["close"]["replicas"]
    tick = [r["tick_ema_s"] for r in health.values()
            if r.get("tick_ema_s") is not None]
    at_close = next(iter(health.values()), {})
    end_to_end = {"serve_tok_s": stats.rate(in_window, t_open, t_end)}
    if ttft:
        end_to_end["ttft_ms_p90"] = stats.percentile(ttft, 90)
    if itl:
        end_to_end["itl_ms_p95"] = stats.percentile(itl, 95)
    return {
        "attempted": len(records),
        "failed": len(failed),
        "numbers": numbers,
        "setup_s": got["setup_s"],
        "window_s": t_end - t_open,
        "memory_peak_bytes": memory_peak,
        "end_to_end": end_to_end,
        "facts": {
            "requests": len(records),
            "tokens_in_window": in_window,
            "window_s": t_end - t_open,
            "window_opened_after_ramp_s": t_open - t_start,
            "tokens_at_fixed_cuts": at_fixed_cuts,
            "serve_flops_in_window": work,
            "tick_ema_ms": 1e3 * tick[0] if tick else None,
            "loadgen_late_ms": late,
            "requests_due_in_window": len(due_in),
            "token_gaps_in_window": len(itl),
            "ttft_ms_p50": stats.percentile(ttft, 50) if ttft else None,
            "ttft_ms_mean": sum(ttft) / len(ttft) if ttft else None,
            "ttft_ms_max": max(ttft) if ttft else None,
            "in_flight_at_open": in_flight(t_start),
            "in_flight_at_half": in_flight((t_start + t_close) / 2),
            "itl_ms_p50": stats.percentile(itl, 50) if itl else None,
            "programs_warmed": n_programs,
            "reference_s": reference_s,
            "tokens_compared": gaps["tokens_compared"],
            "sampled_tokens_compared": gaps["sampled_tokens_compared"],
            "in_flight_at_close": in_flight(t_close),
            "free_pages_at_close": at_close.get("free_pages"),
            "queue_depth_at_close": at_close.get("queue_depth"),
            "active_rows_at_close": at_close.get("active_rows"),
            "first_errors": "; ".join(str(r.error) for r in failed[:3]),
        },
        "health": got["health"],  # both bodies, unaltered
        "trace": form,
        "sample": sample,  # tools and tests read the control over the same
    }
