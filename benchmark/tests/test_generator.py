"""Traffic is a pure function of (mix, seed); sizes stay in their ranges."""

import itertools
import json
import os

import numpy as np

from benchmark import generator, harness


def _mix(name):
    with open(os.path.join(harness.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _take(queues, n):
    return [list(itertools.islice(q, n)) for q in queues]


def test_closed_loop_same_seed_same_requests():
    mix = _mix("closed16-mixed")
    a = _take(generator.closed_loop_requests(mix, 2**31 + 11, 50304), 5)
    b = _take(generator.closed_loop_requests(mix, 2**31 + 11, 50304), 5)
    c = _take(generator.closed_loop_requests(mix, 2**31 + 12, 50304), 5)
    assert a == b
    assert a != c
    assert len(a) == mix["clients"]


def test_closed_loop_lengths_in_range_and_same_multiset_for_every_seed():
    mix = _mix("closed16-mixed")
    sizes = generator.request_sizes(mix)
    assert sizes[:, 0].min() >= 32 and sizes[:, 0].max() <= 512
    assert sizes[:, 1].min() >= 64 and sizes[:, 1].max() <= 192
    per = mix["cycle_requests"] // mix["clients"]

    def multiset(seed):
        # the second cycle: each client's very first request is cut short
        reqs = _take(generator.closed_loop_requests(mix, seed, 50304), 2 * per)
        return sorted((len(p), n) for q in reqs for p, n in q[per:])

    assert multiset(1) == multiset(2**31 + 5) == sorted(map(tuple, sizes))
    first = [q[0] for q in _take(
        generator.closed_loop_requests(mix, 9, 50304), 1)]
    assert all(1 <= n <= 192 for _, n in first)
    ids = [t for q in _take(generator.closed_loop_requests(mix, 3, 50304), 2)
           for p, _ in q for t in p]
    assert min(ids) >= 1 and max(ids) < 50304


def test_bert_batch_is_seeded_rows_differ_and_positions_are_shard_local():
    job = _mix("mlm-dp4-b512")
    cfg = {"vocab_size": 30528, "type_vocab_size": 2}
    a = generator.bert_batch(job, 2**31 + 1, cfg, shards=4)
    b = generator.bert_batch(job, 2**31 + 1, cfg, shards=4)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert len({r.tobytes() for r in a["src_ids"]}) == 512
    rows = 512 // 4
    assert a["mask_pos"].max() < rows * 128 and a["mask_pos"].min() >= 0
    assert a["mask_pos"].shape == (512 * 16, 1)
    # each sequence's masked positions fall inside that sequence
    seq_of = a["mask_pos"][:, 0] // 128
    assert np.array_equal(seq_of, np.repeat(np.arange(512) % rows, 16))


def test_window_is_made_of_token_stamps():
    lane = harness.load_module("runners", "decode_lane.py")
    tokens = lane.Stamped(on_first=lambda t: firsts.append(t))
    firsts = []
    for t in (5, 6, 7):
        tokens.append(t)
    assert list(tokens) == [5, 6, 7] and len(tokens.stamps) == 3
    assert firsts == tokens.stamps[:1]
    assert tokens[:-1] == [5, 6]  # what the engine re-prefills from
    records = [
        # first token before the window, two gaps end inside it
        {"t_submit": 0.0, "stamps": [1.0, 2.1, 2.3, 9.0]},
        # submitted and first token inside; last gap ends at the close
        {"t_submit": 2.0, "stamps": [2.5, 2.7, 3.0]},
        {"t_submit": 2.9, "stamps": []},
    ]
    win = lane.window_numbers(records, t_open=2.0, t_end=3.05)
    assert win["tokens"] == 5 and win["t_close"] == 3.0
    assert abs(win["seconds"] - 1.0) < 1e-12
    assert sorted(round(g) for g in win["gaps_ms"]) == [200, 200, 300, 1100]
    assert [round(t) for t in win["ttft_ms"]] == [500]
