"""The benchmark's one entry.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it finds the cell in ``BENCHMARK.json``, the cell's
configuration, traffic mix and limits by their names under ``perfbench/``,
sets the program up, warms the cell's own shapes (that is ``setup_s``, less
the call in which the TPU runtime starts),
measures for ``--seconds``, compares what the timed path produced with the
plain reference, and prints ONE JSON object as the last line of standard
output. It measures on a device listed in ``perfbench/peaks.json`` or not at
all. See ``perfbench/README.md``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    config: dict
    traffic: dict
    limits: dict
    scratch: str
    t0: float = T0
    runtime_start_s: float = 0.0  # the call in which the TPU runtime starts
    marks: dict = dataclasses.field(default_factory=dict)

    def mark(self, phase: str) -> None:
        """Seconds from the interpreter's start to the end of a set-up phase;
        they go into the line's ``info`` as ``setup_at_<phase>_s``."""
        self.marks[phase] = time.perf_counter() - self.t0


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"perfbench: no workload {name!r} in BENCHMARK.json; it has "
            f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_of_cell(bench: dict, kind: str, cell: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    """The metric's reader: ``layer_metrics/<name>.py``, or the file of the
    name's stem (``device_idle_share.py`` reads ``device_idle_share.<mix>``
    for every mix that brings no reader of its own)."""
    folder = HERE / "layer_metrics"
    path = folder / f"{name}.py"
    if not path.exists():
        path = folder / f"{name.rpartition('.')[0]}.py"
    if not path.is_file():
        raise SystemExit(f"perfbench: no reader under {folder} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_device(chips: int, peaks: dict) -> tuple[dict, dict]:
    """The accelerator as JAX reports it, and its row of peaks; exits
    non-zero when there is none, too few chips, or no peak on record."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform == "cpu" or d.device_kind not in peaks:
        raise SystemExit(
            f"perfbench: needs an accelerator listed in perfbench/peaks.json; "
            f"JAX reports platform {d.platform!r}, device_kind "
            f"{d.device_kind!r}. A number measured here would not be a chip "
            "number.")
    if len(devices) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} chips, JAX reports "
            f"{len(devices)}")
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devices)}, peaks[d.device_kind])


def place_cache() -> None:
    """JAX's persistent compile cache at the program's fixed place inside
    the checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    cached however quickly it compiled."""
    import jax

    from pytorch_distributed_tpu.utils.compile_cache import place_compile_cache

    if place_compile_cache() is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def open_cell(workload: str, seed: int, seconds: float, **kw):
    """(context, BENCHMARK.json, device, its peaks) for one run of a cell:
    finds the cell's files by name, the accelerator, and places the cache."""
    if not (ROOT / "pytorch_distributed_tpu").is_dir():
        raise SystemExit(
            "perfbench: the program (pytorch_distributed_tpu/) is not in this "
            "checkout; the benchmark measures nothing by itself")
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, config = find_cell(bench, workload)
    peaks = {k: v for k, v in load_json(HERE / "peaks.json").items()
             if not k.startswith("_")}
    import jax  # noqa: F401 - timed apart from the look for the chip

    jax_imported = time.perf_counter() - T0
    device, peak = find_device(cell["chips"], peaks)
    runtime_start_s = time.perf_counter() - T0 - jax_imported
    place_cache()
    limits_path = HERE / "limits" / f"{workload}.json"
    ctx = Context(
        workload=workload, seed=seed, seconds=seconds,
        trace=kw.pop("trace", False), chips=cell["chips"],
        config=load_json(ROOT / config["file"]),
        traffic=load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
        limits=load_json(limits_path)["limits"],
        scratch=str(ROOT / ".cache" / "perfbench"), **kw)
    ctx.runtime_start_s = runtime_start_s
    ctx.marks["jax_imported"] = jax_imported
    ctx.mark("device_found")
    return ctx, bench, device, peak


def execute(ctx: Context, bench: dict, device: dict | None, peak: dict | None):
    """Drive one run and build the result line (a dict)."""
    driver = importlib.import_module(f"perfbench.drivers.{ctx.traffic['driver']}")
    res = driver.run(ctx)

    from perfbench import compare, trace

    numbers = res["numbers"]
    correct = compare.verdict(numbers) and res["failed"] == 0
    res.update(peak=peak, config=ctx.config, model=ctx.config["model"],
               chips=ctx.chips)
    metrics: dict = {}
    if not ctx.trace:
        # set-up is everything from the interpreter's start to the window's,
        # less the one call in which the TPU runtime starts: 7-11 s that
        # vary with the host and that nothing in the checkout can change
        values = dict(res["end_to_end"],
                      setup_s=res["setup_s"] - ctx.runtime_start_s)
        for m in metrics_of_cell(bench, "end_to_end", ctx.workload):
            if m["name"] not in values:
                raise SystemExit(
                    f"perfbench: driver reported no {m['name']!r} in "
                    f"{ctx.workload}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in metrics_of_cell(bench, "per_layer", ctx.workload):
            value = load_reader(m["name"])(res)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": bool(correct),
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": dict(device or {}, memory_peak_bytes=res["memory_peak_bytes"]),
    }
    if ctx.trace and res.get("trace") is not None:
        busy_s, window_s = trace.busy_and_window(res["trace"])
        line["device"].update(busy_s=busy_s, window_s=window_s)
        line["breakdown"] = {
            "device_ops": trace.top_device_ops(res["trace"]),
            "idle_gaps": trace.idle_gaps(res["trace"]),
        }
    line["info"] = {k: v for k, v in res["facts"].items()
                    if isinstance(v, (int, float, str))}
    line["info"].update(
        {f"setup_at_{k}_s": v for k, v in ctx.marks.items()},
        setup_with_runtime_start_s=res["setup_s"],
        runtime_start_s=ctx.runtime_start_s)
    line["numbers"] = {  # each number compared, beside its limit: last
        k: dict(n, value=n["value"] if compare.finite(n["value"]) else None)
        for k, n in numbers.items()}
    return line


def report(line: dict) -> None:
    for name, n in line["numbers"].items():
        extra = f" (at {n['leaf']})" if n.get("leaf") else ""
        print(f"compared {name}: {n['value']!r} limit {n['limit']!r}{extra}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    ctx, bench, device, peak = open_cell(
        args.workload, args.seed, args.seconds, trace=bool(args.trace))
    report(execute(ctx, bench, device, peak))
    return 0


if __name__ == "__main__":
    sys.exit(main())
