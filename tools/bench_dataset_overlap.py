"""Dataset ingestion/compute overlap A/B (VERDICT r2 item 5 'bench mode').

Generates a MultiSlot text corpus, trains the same model via
train_from_dataset with prefetch OFF (PT_DATASET_PREFETCH=0) and ON, and
prints one JSON line with wall times, speedup, and the measured
input-bound fraction.  Works on CPU or chip:

    PYTHONPATH=/root/repo                python tools/bench_dataset_overlap.py        # CPU
    PYTHONPATH=/root/repo PT_OVERLAP_TPU=1 python tools/bench_dataset_overlap.py        # chip
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if not os.environ.get("PT_OVERLAP_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

from paddle_tpu import fluid  # noqa: E402
from paddle_tpu.fluid.executor import Scope, scope_guard  # noqa: E402

N_ROWS = int(os.environ.get("PT_OVERLAP_ROWS", "30000"))
BATCH = int(os.environ.get("PT_OVERLAP_BATCH", "512"))
DENSE = 256  # wide dense slot: real parse+postprocess cost per batch
EPOCHS = 3


N_SHARDS = 4  # file-level parser parallelism (dataset.set_thread)


def write_corpus(dirpath):
    rng = np.random.RandomState(0)
    paths = [os.path.join(dirpath, f"part-{i}.txt") for i in range(N_SHARDS)]
    handles = [open(p, "w") for p in paths]
    for i in range(N_ROWS):
        x = rng.uniform(-1, 1, DENSE)
        y = 1 if x[:8].sum() > 0 else 0
        handles[i % N_SHARDS].write(
            f"{DENSE} " + " ".join(f"{v:.6f}" for v in x) + f" 1 {y}\n")
    for h in handles:
        h.close()
    return paths


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[DENSE], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=256, act="relu")
        h = fluid.layers.fc(h, size=256, act="relu")
        sm = fluid.layers.softmax(fluid.layers.fc(h, size=2))
        loss = fluid.layers.mean(fluid.layers.cross_entropy(sm, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def run(paths, prefetch, threads=1):
    main, startup, loss = build()
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(BATCH)
    ds.set_thread(threads)
    ds.set_use_var([main.global_block().var("x"),
                    main.global_block().var("y")])
    ds.set_filelist(paths)
    os.environ["PT_DATASET_PREFETCH"] = str(prefetch)
    s = Scope()
    with scope_guard(s):
        exe = fluid.Executor(fluid.CPUPlace()
                             if not os.environ.get("PT_OVERLAP_TPU")
                             else fluid.TPUPlace(0))
        exe.run(startup)
        exe.train_from_dataset(program=main, dataset=ds)  # warm compile
        t0 = time.perf_counter()
        for _ in range(EPOCHS):
            exe.train_from_dataset(program=main, dataset=ds)
        wall = time.perf_counter() - t0
    return wall, getattr(exe, "last_dataset_stats", None)


def main():
    with tempfile.TemporaryDirectory() as td:
        paths = write_corpus(td)
        sync_wall, _ = run(paths, 0, threads=1)
        # measure the serial pipeline's input-bound fraction with a
        # prefetcher of depth 1 and one parser (no overlap headroom)
        base_wall, base_stats = run(paths, 1, threads=1)
        pre_wall, stats = run(paths, 4, threads=N_SHARDS)
    rec = {
        "metric": "dataset_overlap_speedup",
        "value": round(sync_wall / pre_wall, 3),
        "unit": "x",
        "sync_wall_s": round(sync_wall, 3),
        "prefetch_wall_s": round(pre_wall, 3),
        "parser_threads": N_SHARDS,
        "steps_per_epoch": N_ROWS // BATCH,
        # the mechanism's direct measurement: fraction of the step loop
        # blocked waiting for input.  On CPU the wall-clock gain is masked
        # by core contention (the XLA step saturates the host); on TPU the
        # step runs on-chip, so this fraction converts into wall time.
        "input_bound_fraction_serial": (base_stats or {}).get(
            "input_bound_fraction"),
        "input_bound_fraction_overlapped": (stats or {}).get(
            "input_bound_fraction"),
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
