"""One cell, once, in one process:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name (see README.md): the cell's file names its
configuration, traffic mix and runner; `BENCHMARK.json` names the metrics.
The last line of stdout is the one JSON object the driver reads, checked by
`contract.check_last_line` before it is written; everything else goes to
stderr.  No TPU, or fewer chips than the cell asks for, is a nonzero exit
and no result — except under `--rehearse` (tests and the builder only),
which runs the cell's tiny rehearsal sizes on the CPU and says
`"platform": "cpu"`."""

import time

T0 = time.perf_counter()   # as near the start of the process as code gets

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import contract  # noqa: E402

SCRATCH = os.path.join(ROOT, ".chipbench_scratch")


def log(msg: str) -> None:
    print(f"[chipbench +{time.perf_counter() - T0:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def _load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"BENCHMARK.json names {name!r}, but "
            f"{os.path.relpath(path, ROOT)} does not exist")
    with open(path) as f:
        return json.load(f)


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _overlay(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


class _Profile:
    """`with ctx.profile() as prof:` traces what runs inside; afterwards
    `prof.result` is {"trace": reduced trace, "window_s": host seconds of
    the traced part}."""

    def __init__(self, ctx):
        self.ctx, self.result = ctx, None

    def __enter__(self):
        self.dir = os.path.join(SCRATCH, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        if not self.ctx.rehearse:
            import jax

            jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        from chipbench import trace_reduce

        window_s = time.perf_counter() - self.t0
        if exc[0] is not None:
            return False
        if self.ctx.rehearse:
            # no chip, no device trace: the readers are driven on the trace
            # this kind of cell recorded on the chip
            trace = trace_reduce.load_recorded(os.path.join(
                HERE, "recorded", self.ctx.cell["recorded_trace"]))
        else:
            import jax

            jax.profiler.stop_trace()
            trace = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.dir))
            trace["device_kind"] = self.ctx.devices[0].device_kind
            keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
            if keep:   # the builder's way to record a trace for the tests
                trace_reduce.save_recorded(trace_reduce.trim(trace), keep)
            shutil.rmtree(self.dir, ignore_errors=True)
        self.result = {"trace": trace, "window_s": window_s}
        return False


class Context:
    """What a runner is handed."""

    def __init__(self, args, cell, sizes, mix, devices):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.control = args.control
        self.cell, self.sizes, self.mix = cell, sizes, mix
        self.devices = devices
        self.log = log
        self.setup_s = None

    def span(self, name: str):
        """A host span of the benchmark's own, on the profiler's clock."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def profile(self):
        return _Profile(self)

    def window_opens(self):
        self.setup_s = time.perf_counter() - T0
        log(f"window opens: setup_s {self.setup_s:.2f}")

    def window_closed(self):
        log("window closed")

    def memory_peak(self) -> int:
        """The fullest chip's peak: what the allocator held at its peak
        plus what it had set aside for the programs' temporaries —
        `peak_bytes_in_use` alone leaves those out (PERF.md section 7)."""
        stats = [d.memory_stats() or {} for d in self.devices]
        log(f"memory_stats of the fullest chip: "
            f"{max(stats, key=lambda s: s.get('peak_bytes_in_use', 0))}")
        peaks = [s.get("peak_bytes_in_use", 0)
                 + s.get("peak_bytes_reserved", 0) for s in stats]
        if self.rehearse and not any(peaks):
            return 1   # the CPU backend reports none
        return int(max(peaks))


def read_per_layer(bench, workload, raw) -> dict:
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload["name"] not in m["workloads"]:
            continue
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"BENCHMARK.json names the per-layer metric {m['name']!r}, "
                f"but {os.path.relpath(path, ROOT)} does not exist")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(raw)
        if value is None:
            log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(argv=None) -> dict:
    """Run one cell; returns the checked last-line object."""
    p = argparse.ArgumentParser(prog="chipbench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU; tests and the builder only")
    p.add_argument("--control", action="store_true",
                   help="also read the lower-precision control (builder)")
    args = p.parse_args(argv)

    bench = contract.load_benchmark(ROOT)
    missing = contract.check_benchmark(bench, ROOT)
    if missing:
        raise FileNotFoundError(f"BENCHMARK.json names files that do not "
                                f"exist: {missing}")
    workload = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        raise KeyError(f"BENCHMARK.json has no workload {args.workload!r}; "
                       f"it has {[w['name'] for w in bench['workloads']]}")
    cell = _load("cells", workload["name"])
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == workload["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        sizes = json.load(f)
    mix = _load("traffic", workload["traffic"])
    if (cell["config"], cell["traffic"], cell["chips"]) != (
            workload["config"], workload["traffic"], workload["chips"]):
        raise ValueError(f"chipbench/cells/{workload['name']}.json and "
                         f"BENCHMARK.json disagree on config, traffic or "
                         f"chips")
    if args.rehearse:
        sizes = _overlay(sizes, cell["rehearse"].get("sizes"))
        mix = _overlay(mix, cell["rehearse"].get("traffic"))
        cell = _overlay(cell, cell["rehearse"].get("cell"))

    import jax

    from easydist_tpu import config as edconfig
    from easydist_tpu.utils.jax_cache import configure_jax_cache

    # caches inside the checkout, at fixed paths: XLA's persistent cache
    # (`.jax_cache` unless JAX_COMPILATION_CACHE_DIR is set), the program's
    # strategy and discovery stores (`.easydist_cache`), and a PerfDB of
    # this benchmark's own so that none left in $HOME steers the solver
    os.makedirs(SCRATCH, exist_ok=True)
    cache_dir = configure_jax_cache(min_compile_secs=0.5)
    edconfig.enable_compile_cache = True
    edconfig.prof_db_path = os.path.join(SCRATCH, "perf.db")
    if args.rehearse:
        devices = jax.devices("cpu")
    else:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise SystemExit(f"chipbench: needs a TPU; JAX reports "
                             f"{devices[0].platform!r} x{len(devices)}")
    if len(devices) < workload["chips"]:
        raise SystemExit(f"chipbench: workload {workload['name']!r} needs "
                         f"{workload['chips']} chips, JAX reports "
                         f"{len(devices)}")
    devices = list(devices[:workload["chips"]])
    log(f"{workload['name']} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {devices[0].device_kind!r} x{len(devices)}; "
        f"jax cache {cache_dir}")

    ctx = Context(args, cell, sizes, mix, devices)
    runner = importlib.import_module("chipbench.runners." + cell["runner"])
    raw = runner.run(ctx)
    raw.update(cell=cell, mix=mix, sizes=sizes, chips=workload["chips"],
               device_kind=devices[0].device_kind, rehearse=args.rehearse)

    metrics = {"setup_s": {"value": ctx.setup_s, "unit": "s"}}
    units = contract.cell_metrics(bench, workload["name"], True)
    for name, value in raw["e2e"].items():
        if name in units:
            metrics[name] = {"value": float(value), "unit": units[name]}
        else:   # measured, but no metric of this cell in BENCHMARK.json
            log(f"not reported: {name} = {value}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": raw["memory_peak_bytes"]}
    obj = {"correct": bool(raw["correct"]), "attempted": raw["attempted"],
           "failed": raw["failed"], "metrics": metrics, "device": device}
    if args.trace:
        from chipbench import trace_reduce

        trace = raw["trace"]
        busy = trace_reduce.busy(trace["trace"], workload["chips"])
        if args.rehearse:   # a recorded trace against this run's own clock
            trace["window_s"] = max(trace["window_s"],
                                    1.25 * max(busy["per_chip_s"]))
            raw["device_kind"] = trace["trace"]["device_kind"]
        raw["busy"] = busy
        device["busy_s"] = busy["busy_s"]
        device["window_s"] = trace["window_s"]
        metrics.update(read_per_layer(bench, workload, raw))
        obj["breakdown"] = trace_reduce.breakdown(trace["trace"])
        log(f"traced {trace['window_s']:.3f} s, busy per chip "
            f"{[round(b, 3) for b in busy['per_chip_s']]}")
    obj["check"] = {k: raw["check"][k] for k in ("numbers", "control")
                    if raw["check"].get(k) is not None}
    contract.check_last_line(obj, workload, bool(args.trace), bench)
    return obj


def main() -> None:
    # stdout is kept aside and file descriptor 1 pointed at stderr, so that
    # nothing a library prints can follow (or precede) the one result line
    real_stdout = os.dup(1)
    os.dup2(2, 1)
    try:
        obj = execute()
    except SystemExit as e:
        print(e, file=sys.stderr, flush=True)
        os._exit(e.code if isinstance(e.code, int) and e.code else 2)
    except BaseException:
        import traceback

        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    sys.stderr.flush()
    os.write(real_stdout, (json.dumps(obj) + "\n").encode())
    os._exit(0)


if __name__ == "__main__":
    main()
