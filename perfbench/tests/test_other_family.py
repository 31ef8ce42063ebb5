"""A configuration that is not GPT-2 is files and entries, no edit.

The second family exists only here. Its ``model`` block holds HF-style keys
and no GPT-2 key; its plain reference and its count, registered from the
test as ``reference/hfkeys.py`` and ``counts/hfkeys.py`` would be found,
translate the block and call GPT-2's; the program's preset is the tiny GPT-2
of ``tiny.py``, held to the block by the ``program`` block's pairs. Both
drivers run it end to end on the CPU and read ``correct: true``; the work
they count is GPT-2's count of the same work to the last digit; a pair that
differs, or a family without a count module, stops the run; and a reader
defined here takes a counter's difference over the window from
``res["health"]``, which no field of ``facts`` carries.
"""

import sys
import time
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import run  # noqa: E402
from perfbench.counts import gpt2 as gpt2_count  # noqa: E402
from perfbench.drivers import serve, train  # noqa: E402
from perfbench.reference import gpt2 as gpt2_reference  # noqa: E402
from perfbench.tests import tiny  # noqa: E402
from perfbench.tests.test_control import ctx_for  # noqa: E402

KEYS = {"hidden_size": "n_embd", "num_hidden_layers": "n_layer",
        "num_attention_heads": "n_head",
        "max_position_embeddings": "n_positions"}
GPT2_KEYS = set(KEYS.values()) | {"n_inner"}


def as_gpt2(model: dict) -> dict:
    """The block in GPT-2's keys: what this family's modules do first."""
    assert not GPT2_KEYS & set(model), model
    return {KEYS.get(k, k): v for k, v in model.items()}


def hf_config(base: dict) -> dict:
    """``base`` (a tiny GPT-2 stand-in) as a file of the family ``hfkeys``."""
    back = {v: k for k, v in KEYS.items()}
    model = {back.get(k, k): v for k, v in base["model"].items()
             if k != "n_inner"}
    model["model_type"] = "hfkeys"
    prog = base["program"]
    return dict(
        base, name="tiny-hfkeys", reference="hfkeys", model=model,
        program=dict(prog, **{
            f"{path}_holds": {f: back.get(k, k)
                              for f, k in prog[f"{path}_holds"].items()}
            for path in ("train", "serve")}))


def translated(module, names):
    """``module``'s functions under this family's name, each translating the
    ``model`` block among its positional arguments before GPT-2's runs."""
    mod = types.ModuleType(module.__name__.replace("gpt2", "hfkeys"))
    for name in names:
        def fn(*args, _real=getattr(module, name), **kw):
            return _real(*(as_gpt2(a) if isinstance(a, dict)
                           and "hidden_size" in a else a for a in args), **kw)
        setattr(mod, name, fn)
    return mod


@pytest.fixture
def family(monkeypatch):
    """Registers the family's modules where ``reference.of`` and ``flops.of``
    look; ``family(count=False)`` leaves the count module out."""
    def register(count=True):
        mods = [translated(gpt2_reference, (
            "init_params", "logits_at", "train_reference", "leaf_norms"))]
        if count:
            mods.append(translated(gpt2_count, (
                "n_params", "train_flops_per_token", "serve_flops_span",
                "flash_attention_work")))
        for mod in mods:
            monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return register


def hf_ctx(kind, tmp_path, **model_changes):
    ctx = ctx_for(kind, 5, tmp_path)
    ctx.config = hf_config(ctx.config)
    ctx.config["model"].update(model_changes)
    return ctx


def stops(case, driver, kind, tmp_path) -> bool:
    """The two cases in which a run stops non-zero, having set nothing up;
    False for any other case."""
    if case == "no_count_module":
        ctx, says = hf_ctx(kind, tmp_path), ("perfbench/counts/hfkeys.py",)
    elif case == "pair_differs":
        ctx = hf_ctx(kind, tmp_path, num_hidden_layers=3)
        says = ("n_layer=2", "num_hidden_layers=3")
    else:
        return False
    with pytest.raises(SystemExit) as stop:
        driver.run(ctx)
    assert stop.value.code not in (0, None)
    assert all(word in str(stop.value.code) for word in says), stop.value
    assert not ctx.marks, ctx.marks  # no phase of set-up was reached
    return True


@pytest.mark.parametrize("case", ["runs", "pair_differs", "no_count_module"])
def test_training_driver_takes_another_family(case, family, tmp_path):
    family(count=case != "no_count_module")
    if stops(case, train, "train", tmp_path):
        return
    line = run.execute(
        hf_ctx("train", tmp_path), tiny.TINY_BENCH, None, None)
    assert line["correct"] is True, line["numbers"]
    assert line["info"]["train_flops_per_token"] == \
        gpt2_count.train_flops_per_token(
            tiny.TINY_MODEL, tiny.TINY_TRAIN["seq_len"])


def tokens_sent_in_window(res):
    """A reader as a later PR would add one under ``layer_metrics/``: a
    counter of the program's that ``facts`` does not carry, as its difference
    between the two ends of the window."""
    sent = [res["health"][end]["server"]["counters"]["tokens_sent"]
            for end in ("open", "close")]
    return sent[1] - sent[0] or None


@pytest.mark.parametrize("case", ["runs", "pair_differs", "no_count_module"])
def test_serving_driver_takes_another_family(case, family, tmp_path,
                                             monkeypatch):
    family(count=case != "no_count_module")
    if stops(case, serve, "backlog", tmp_path):
        return

    # watch the driver: every client record, when each /healthz body came,
    # the window it cut, and what it handed the readers
    records, reads, kept = [], [], {}

    class Watched(serve.Record):
        __slots__ = ()

        def __init__(self, req):
            super().__init__(req)
            records.append(self)

    async def healthz(host, port, real=serve.healthz):
        body = await real(host, port)
        reads.append(time.perf_counter())
        return body

    def window_of(*args, real=serve.window_of):
        kept["window"] = real(*args)
        return kept["window"]

    def driver_run(ctx, real=serve.run):
        kept["res"] = real(ctx)
        return kept["res"]

    monkeypatch.setattr(serve, "Record", Watched)
    monkeypatch.setattr(serve, "healthz", healthz)
    monkeypatch.setattr(serve, "window_of", window_of)
    monkeypatch.setattr(serve, "run", driver_run)

    ctx = hf_ctx("backlog", tmp_path)
    line = run.execute(ctx, tiny.TINY_BENCH, None, None)
    assert line["correct"] is True, line["numbers"]
    res = kept["res"]

    # the work in the window, counted again by GPT-2's count in GPT-2's keys
    t_open, t_end = kept["window"]
    inside = lambda t: t_open < t <= t_end  # noqa: E731
    gpt2_model, work = as_gpt2(ctx.config["model"]), 0.0
    for r in records:
        p = len(r.req["body"]["prompt"])
        if r.times and inside(r.times[0]):
            work += gpt2_count.serve_flops_span(gpt2_model, 0, p)
        n_dec = sum(1 for t in r.times[1:] if inside(t))
        work += gpt2_count.serve_flops_span(gpt2_model, p, p + n_dec)
    assert work > 0 and line["info"]["serve_flops_in_window"] == work

    # both bodies whole, and a counter's difference over the window: within
    # one burst (a token a row) a read of what the clients counted between
    assert set(res["health"]) == {"open", "close"}
    for body in res["health"].values():
        assert {"replicas", "server", "timers"} <= set(body), sorted(body)
    assert len(reads) == 2
    counted = sum(1 for r in records for t in r.times
                  if reads[0] < t <= reads[1])
    slots = ctx.traffic["engine"]["slots"]
    sent = tokens_sent_in_window(res)
    assert counted > 10 * slots, counted
    assert abs(sent - counted) <= 2 * slots, (sent, counted)
