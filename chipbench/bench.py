"""Run one benchmark cell once, on the chip this process finds.

    python chipbench/bench.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name:
``workloads/<cell>.json`` names its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<mix>.json``, whose
``kind`` picks the driver ``kinds/<kind>.py``) and the numbers compared
for ``correct`` with their limits.  The configuration's ``reference``
names its architecture module, ``references/<reference>.py``: the weights,
the plain reference and the operation counts (``CONTRACT``).
``BENCHMARK.json`` at the checkout's root says which metrics the cell
reports; each per-layer metric is read by ``metrics/<metric>.py``.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` it carries its per-layer metrics,
read from the program's spans and counters and from a profiler trace of
the window.  Either way the run checks what the timed path produced
against a plain reference, and prints each number compared beside its
limit as its last lines on standard error and under ``checks``, the last
key of the result.

Off a TPU, with fewer chips than the cell asks for, or with Pallas kernels
in interpret mode, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache"
# JAX's persistent compilation cache sits at a fixed path inside the
# checkout unless the environment names one; the program takes this one
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE / "jax"))
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


class ChipMissing(RuntimeError):
    """No TPU, too few chips, or kernels that would run interpreted."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path; a metric name such as ``mfu.serve`` holds a
    dot, so readers are not importable by name."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chip(chips: int):
    """The devices a run may measure on: at least ``chips`` TPUs whose
    Pallas kernels compile through Mosaic.  Raises ChipMissing otherwise."""
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise ChipMissing(f"no TPU: JAX found {dev.platform} devices")
    if ops.interpret_default():
        raise ChipMissing("Pallas kernels would run in interpret mode")
    if len(devices) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, found"
                          f" {len(devices)}")
    return devices[:chips]


def cell_files(name: str, root: Path = HERE) -> dict:
    """The data files of one cell, read by name."""
    work = load_json(root / "workloads" / f"{name}.json")
    return {
        "work": work,
        "config": load_json(root / "configs" / f"{work['config']}.json"),
        "mix": load_json(root / "traffic" / f"{work['traffic']}.json"),
    }


# What an architecture module (``references/<name>.py``) gives, each a
# function of the configuration file's ``sizes``:
#   make(sizes, seed, spec): the program's parameter tree on the device,
#       in the layout and dtypes of ``spec`` (``ShapeDtypeStruct``s);
#       raises where ``spec`` differs
#   last_logits(sizes, seed, tokens, mode=, dtype=): the reference's
#       next-token logits; ``mode="fp8"`` is the control
#   position_stats(sizes, seed, tokens, positions, lookup, mode=, dtype=):
#       the largest logit, its token, and the logits of ``lookup`` at
#       each position
#   prefill_flops(sizes, seq_len): model FLOPs of one prefill
#   prefill_attention(sizes, seq_len): (flops, bytes) of its attention
#   tokens_flops(sizes, q_len, kv_len, unembedded=): model FLOPs of new
#       tokens at the end of a context
#   unembed_flops(sizes): FLOPs of one position's unembedding
#   paged_step(sizes, rows): (flops, bytes) of the paged kernel over one
#       engine step of every layer
CONTRACT = ("make", "last_logits", "position_stats", "prefill_flops",
            "prefill_attention", "tokens_flops", "unembed_flops",
            "paged_step")
# ``sizes`` keys named otherwise in the program's configuration
PROGRAM_NAMES = {"head_dim": "hd"}


def reference(conf: dict, root: Path = HERE):
    """The architecture module a configuration file names.  Raises where
    it is missing or lacks a function of ``CONTRACT``."""
    path = root / "references" / f"{conf['reference']}.py"
    if not path.is_file():
        raise ValueError(f"no architecture module {path}")
    mod = load_module(path)
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{path} lacks {missing}")
    return mod


def model_config(conf: dict):
    """The program's configuration for a configuration file: the registry
    entry with the file's overrides, checked against every one of the
    file's sizes."""
    from repro.configs import get_config

    cfg = get_config(conf["registry"]).with_(**conf.get("overrides", {}))
    absent = object()
    unknown, wrong = [], {}
    for key, want in conf["sizes"].items():
        have = getattr(cfg, PROGRAM_NAMES.get(key, key), absent)
        if have is absent:
            unknown.append(key)
        elif have != want:
            wrong[key] = (have, want)
    if unknown:
        raise ValueError(f"{conf['registry']}: the program's configuration"
                         f" has no {unknown}")
    if wrong:
        raise ValueError(f"{conf['registry']}: the program's configuration"
                         f" differs from the file (program, file): {wrong}")
    return cfg


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """Entries of ``bench[group]`` that the cell reports."""
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


class Ctx:
    """What a driver (``kinds/<kind>.py``) is given for one run."""

    def __init__(self, args, files, cfg, devices, reference):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.work, self.mix = files["work"], files["mix"]
        self.sizes = dict(files["config"]["sizes"])
        self.cfg = cfg
        # the configuration's architecture module (``CONTRACT``)
        self.reference = reference
        self.devices = devices
        self.trace_dir = CACHE / "trace"
        self.t_start = T_START
        self.setup_end = None
        self.trace_window = None   # (start, stop) perf_counter seconds
        # also judge the float8 control in the program's place (control.py)
        self.control = False

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    @staticmethod
    def annotate(name: str):
        """A host span in the profiler's trace (and a no-op without one)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def end_setup(self) -> float:
        self.setup_end = time.perf_counter()
        return self.setup_end - self.t_start

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self.trace_window = [time.perf_counter(), None]

    def stop_trace(self) -> None:
        import jax

        self.trace_window[1] = time.perf_counter()
        jax.profiler.stop_trace()

    def memory_peak_bytes(self) -> int:
        """Peak device memory of the fullest chip.  On a TPU the
        executables' temporaries are reserved apart from the buffers in
        use, so both peaks count."""
        def peak(d):
            stats = d.memory_stats() or {}
            return (int(stats.get("peak_bytes_in_use", 0))
                    + int(stats.get("peak_bytes_reserved", 0)))

        return max(peak(d) for d in self.devices)

    def install_plan_cache(self) -> None:
        """Hand the program's AutoChunk transform a plan cache at a fixed
        path in the checkout, so that a warm run replays the searched plan
        and its kernel tiles instead of searching and timing them again.

        The model's wrapper builds its transform with no cache argument;
        this fills that argument in.
        """
        import functools

        import repro.core as core
        from repro.core.plan import PlanCache

        if getattr(core.autochunk, "chipbench_cache", None) is not None:
            return
        plans = PlanCache(str(CACHE / "plans"))
        wrapped = functools.partial(core.autochunk, cache=plans)
        wrapped.chipbench_cache = plans
        core.autochunk = wrapped


def free_device_state() -> None:
    """Drop what the timed path left on the device before the reference."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def top_level_compile_spans() -> list:
    """(name, seconds) of the program's ``compile.*`` spans not nested in
    another ``compile.*`` span."""
    from repro.obs import tracing

    return [(s.name, s.duration) for s in tracing.TRACER.spans()
            if s.name.startswith("compile.")
            and not (s.parent or "").startswith("compile.")]


def read_layer_metrics(entries: list, data: dict, root: Path = HERE) -> dict:
    out = {}
    for m in entries:
        reader = load_module(root / "metrics" / f"{m['name']}.py")
        value = reader.read(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args, root: Path = HERE, bench_file: Path = ROOT / "BENCHMARK.json",
        control: bool = False):
    """One run; returns the result dict (the last line's object).

    ``control`` also judges the float8 control, put in the program's
    place, by the same comparison (see ``control.py``); its verdict comes
    under ``control``.  The benchmark's own runs never read it.
    """
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}")
    files = cell_files(args.workload, root)
    ref = reference(files["config"], root)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = require_chip(cells[args.workload]["chips"])
    dev = devices[0]
    cfg = model_config(files["config"])
    ctx = Ctx(args, files, cfg, devices, ref)
    ctx.control = control
    driver = load_module(root / "kinds" / f"{files['mix']['kind']}.py")
    res = driver.run(ctx)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"]}
    if ctx.trace:
        data = dict(res["layer_data"], sizes=ctx.sizes, reference=ref,
                    device_kind=dev.device_kind,
                    compile_spans=top_level_compile_spans())
        summary = data.get("trace")
        out["metrics"] = read_layer_metrics(
            cell_metrics(bench, args.workload, "per_layer"), data, root)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = summary.breakdown()
    else:
        out["metrics"] = {
            m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in cell_metrics(bench, args.workload, "end_to_end")}
    out["device"] = device
    if "control" in res:
        out["control"] = {"correct": res["control"]["correct"],
                          "checks": checks_dict(res["control"]["checks"])}
    out["checks"] = checks_dict(res["checks"])
    return out


def checks_dict(checks) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in checks}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        out = run(args)
    except ChipMissing as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"[check] {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
