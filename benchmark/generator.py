"""The one general traffic generator: a mix or job file in, inputs out.

A mix is data.  ``kind: "train_job"`` gives one resident batch; ``kind:
"closed_loop"`` gives each client its queue of requests.  Everything is a
pure function of (mix, seed): the same seed gives the same inputs.

Every seed gets the SAME request sizes in the SAME order (the
distribution's quantiles, paired and ordered once by the mix's own
``sizes_seed``) with other token ids and other weights, so runs with
different seeds do the same work.  A closed-loop window of some tens of
long requests holds too few arrivals to average an order out: with the
order drawn from the seed, a replay of the scheduler at the measured step
times has one tree's tokens per second swing by 5-6% (interquartile,
PERF.md section 6): more than any bound may allow.
"""

from __future__ import annotations

import math

import numpy as np


def rng_for(seed, stream):
    """Seeds run to a little over 2**31: SeedSequence takes any size."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _spread_lengths(spec, n):
    """n lengths at the (i + 1/2) / n quantiles of the distribution: the
    same spread for every run, however few requests a cycle has."""
    lo, hi = int(spec["min"]), int(spec["max"])
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo)
    elif spec["dist"] == "log_uniform":
        x = np.exp(math.log(lo) + q * (math.log(hi + 1) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x).astype(np.int64), lo, hi)


def request_sizes(mix):
    """The mix's fixed multiset of (prompt_len, output_len), one row per
    request of one cycle: both spreads, paired at random once and for all
    by the mix's own ``sizes_seed``."""
    rng = rng_for(mix["sizes_seed"], 0)
    n = int(mix["cycle_requests"])
    return np.stack([rng.permutation(_spread_lengths(mix["prompt_len"], n)),
                     rng.permutation(_spread_lengths(mix["output_len"], n))],
                    axis=1)


def closed_loop_requests(mix, seed, vocab_size):
    """Per client, an endless iterator of (prompt ids, max_new_tokens).
    The cycle of sizes is dealt to the clients round-robin in the mix's own
    fixed order; the run's seed gives the token ids and nothing else, so
    every seed queues the same work in the same order (with two dozen
    long requests in a window, another order is another load).  Each
    client's first request (the wave that fills the pool before the window
    opens) keeps only a fixed share of its output, as if it were caught
    part-way: the clients then leave step with each other, as a pool long
    in service has them."""
    sizes = request_sizes(mix)
    order = np.arange(len(sizes))
    clients = int(mix["clients"])
    shares = rng_for(mix["sizes_seed"], 4).uniform(0.1, 1.0, clients)

    def client(c):
        ids = rng_for(seed, 100 + c)
        k = c
        while True:
            p, o = sizes[order[k % len(order)]]
            if k == c:
                o = max(1, int(o * shares[c]))
            yield (ids.integers(1, vocab_size, int(p)).tolist(), int(o))
            k += clients

    return [client(c) for c in range(clients)]


def bert_batch(job, seed, config, shards=1):
    """One synthetic pretraining batch whose rows all differ.  ``mask_pos``
    holds flat positions into the rows of the shard that gathers them."""
    vocab_size = config["vocab_size"]
    type_vocab_size = config["type_vocab_size"]
    rng = rng_for(seed, 2)
    batch, seq = int(job["batch"]), int(job["seq_len"])
    n_masked = int(job["masked_per_seq"])
    if batch % shards:
        raise ValueError(f"batch {batch} does not split over {shards}")
    rows = batch // shards
    # masked positions: n_masked distinct positions in every sequence
    pos_in_seq = np.stack([rng.permutation(seq)[:n_masked]
                           for _ in range(batch)])
    local_row = (np.arange(batch) % rows)[:, None]
    return {
        "src_ids": rng.integers(0, vocab_size, (batch, seq)).astype("int64"),
        "pos_ids": np.tile(np.arange(seq, dtype="int64"), (batch, 1)),
        "sent_ids": rng.integers(0, type_vocab_size,
                                 (batch, seq)).astype("int64"),
        "input_mask": np.ones((batch, seq), dtype="float32"),
        "mask_label": rng.integers(0, vocab_size,
                                   (batch * n_masked, 1)).astype("int64"),
        "mask_pos": (local_row * seq + pos_in_seq).reshape(-1, 1)
        .astype("int64"),
        "labels": rng.integers(0, 2, (batch, 1)).astype("int64"),
    }
