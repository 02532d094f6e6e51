"""What every runner shares: finding a cell's files by name, the chip
check, the clocks, counters, the traced window, the checks behind
``correct`` and the result line.  Nothing here knows a configuration, a
mix or a metric: those are files, found by the names in BENCHMARK.json.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(SystemExit):
    """The machine does not hold the chips the cell asks for."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """A module of the benchmark by its file, so that file names may carry
    the characters of a cell or metric name (``.`` and ``-``)."""
    path = os.path.join(HERE, *parts)
    name = "benchmark_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# the tests point these at a tiny benchmark of their own
BENCHMARK_PATH = os.path.join(ROOT, "BENCHMARK.json")
TRAFFIC_DIRS = [os.path.join(HERE, "traffic")]


def load_benchmark():
    with open(BENCHMARK_PATH) as f:
        return json.load(f)


def load_cell(bench, name):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    for d in TRAFFIC_DIRS:
        path = os.path.join(d, cell["traffic"] + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return cell, config, json.load(f)
    raise SystemExit(f"no traffic file {cell['traffic']}.json")


def use_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout (the path
    is part of the key), unless the environment placed it.  The program
    picks the same directory for itself."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def require_devices(chips):
    """The devices the cell runs on.  Anything but a TPU with at least
    ``chips`` chips ends the run with no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(
            f"benchmark: needs {chips} TPU chip(s), found "
            f"{len(devices)} x {devices[0].platform}: no result")
    return devices[:chips]


def device_report(devices):
    import jax

    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def memory_peak_bytes(devices):
    """Peak bytes on the fullest chip, so far in this process: the peak of
    the buffers in use plus the peak of what loaded programs reserve for
    their scratch.  The runtime counts the two apart (a BERT step that
    reserves 6.7 GB reads 2.9 GB "in use"), and a chip is as full as their
    sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved",
                                 stats.get("bytes_reserved", 0)))
    return int(max(peaks))


def memory_report(devices):
    """The runtime's own memory counters of the first chip, for the log."""
    return {k: v for k, v in (devices[0].memory_stats() or {}).items()
            if isinstance(v, int)}


def peaks_for(kind):
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"benchmark: no peaks recorded for device kind "
                         f"{kind!r}; add it to benchmark/peaks.json with its "
                         f"source")
    return table[kind]


# ---------------------------------------------------------------------------
# counters of the program, flattened to one namespace of numbers
# ---------------------------------------------------------------------------


def counters():
    """{"family{label,values}": number}; a histogram gives ``.sum`` and
    ``.count``."""
    from paddle_tpu import observability as obs

    out = {}
    for name, fam in obs.snapshot().items():
        for labels, v in (fam.get("samples") or {}).items():
            key = f"{name}{{{','.join(map(str, labels))}}}"
            if isinstance(v, dict):
                out[key + ".sum"] = float(v.get("sum", 0.0))
                out[key + ".count"] = float(v.get("count", 0))
            else:
                out[key] = float(v)
    return out


def delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def compiles(snapshot):
    """Executables built so far, over every executor lane (the persistent
    cache's own hits and misses are not builds of a new signature)."""
    return sum(v for k, v in snapshot.items()
               if k.startswith("pt_compile_cache_total{")
               and k.endswith(",miss}") and "xla_persistent" not in k)


def kernel_forms(snapshot):
    """{primitive: {form, ...}} from pt_kernel_dispatch_total."""
    out = {}
    for k, v in snapshot.items():
        if k.startswith("pt_kernel_dispatch_total{") and v:
            primitive, form = k[len("pt_kernel_dispatch_total{"):-1].split(",")
            out.setdefault(primitive, set()).add(form)
    return out


# ---------------------------------------------------------------------------
# the traced window
# ---------------------------------------------------------------------------


def trace_dir():
    path = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(path, ignore_errors=True)
    return path


@contextlib.contextmanager
def tracing(path):
    """Device trace of the enclosed interval: host and Python tracers off,
    so the host is slowed as little as a trace allows."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# correct: every number compared, printed beside its limit
# ---------------------------------------------------------------------------


class Checks:
    def __init__(self):
        self.rows = []

    def limit(self, name, value, limit):
        """``value`` must not pass ``limit`` (a NaN fails)."""
        ok = bool(value <= limit)
        self.rows.append((name, value, f"<= {limit}", ok))
        return ok

    def floor(self, name, value, least):
        ok = bool(value >= least)
        self.rows.append((name, value, f">= {least}", ok))
        return ok

    def equal(self, name, value, want):
        ok = value == want
        self.rows.append((name, value, f"== {want}", ok))
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(ok for *_, ok in self.rows)

    def print(self):
        for name, value, limit, ok in self.rows:
            print(f"CHECK {'ok  ' if ok else 'FAIL'} {name}: {value} "
                  f"(limit {limit})", flush=True)


def percentile(values, q):
    """The q-th percentile, linear between order statistics."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def now():
    return time.perf_counter()
