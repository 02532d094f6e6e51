"""The trace reduction on hand-made event lists: (name, start_ns, dur_ns)."""

from benchmark import trace_reduce as tr

MS = 1_000_000


def test_busy_is_the_union_of_overlapping_intervals():
    events = [("a", 0, 10 * MS), ("b", 5 * MS, 10 * MS),   # 0..15 overlap
              ("c", 20 * MS, 5 * MS),                       # 20..25
              ("d", 21 * MS, 1 * MS)]                       # nested in c
    assert tr.busy_seconds(events) == 0.020
    assert tr.busy_seconds([]) == 0.0
    assert tr.span(events) == (0, 25 * MS)


def test_two_devices_are_reduced_apart():
    dev0 = [("fusion.1", 0, 4 * MS), ("all-reduce.7", 4 * MS, 2 * MS)]
    dev1 = [("fusion.1", 0, 1 * MS)]
    assert tr.busy_seconds(dev0) == 0.006
    assert tr.busy_seconds(dev1) == 0.001


def test_pattern_time_and_module_filter():
    modules = [("jit_decode(1)", 0, 10 * MS), ("jit_prefill(2)", 10 * MS, 10 * MS)]
    ops = [("paged_attention.3", 1 * MS, 2 * MS), ("fusion.9", 3 * MS, 1 * MS),
           ("paged_attention.3", 11 * MS, 5 * MS)]
    assert tr.pattern_seconds(ops, "paged_attention") == (0.007, 2)
    inside = tr.inside_modules(ops, modules, "decode")
    assert tr.pattern_seconds(inside, "paged_attention") == (0.002, 1)
    assert tr.pattern_seconds(ops, "nothing") == (0.0, 0)


def test_self_time_does_not_count_a_loop_body_twice():
    ops = [("while.1", 0, 10 * MS), ("body.2", 1 * MS, 4 * MS),
           ("body.2", 5 * MS, 4 * MS), ("copy.3", 12 * MS, 1 * MS)]
    times = tr.self_times(ops)
    assert times == {"while.1": 0.002, "body.2": 0.008, "copy.3": 0.001}
    assert tr.top_operations(ops, 2) == [["body.2", 0.008], ["while.1", 0.002]]


def test_idle_gaps_are_named_by_the_programs_around_them():
    modules = [("jit_a(1)", 0, 5 * MS), ("jit_b(2)", 9 * MS, 5 * MS)]
    ops = [("x", 0, 5 * MS), ("y", 9 * MS, 5 * MS), ("z", 15 * MS, 1 * MS)]
    gaps = tr.idle_gaps(ops, modules, 5)
    assert gaps[0] == ["jit_a->jit_b", 0.004]
    assert gaps[1] == ["jit_b->jit_b", 0.001]


def test_operations_are_labelled_by_opcode_and_result_type():
    a = ("%fusion.2522 = (bf16[3072]{0:T(1024)(128)(2,1)}, bf16[128,128,3072]"
         "{2,1,0:T(8,128)(2,1)}) fusion(f32[3072,768]{1,0:T(8,128)} %p.1, "
         "bf16[128,128,768]{2,1,0} %g.477), kind=kOutput, calls=%fc.3561")
    b = a.replace("fusion.2522", "fusion.2516").replace("%p.1", "%p.7")
    c = "%all-reduce.5 = f32[768,768]{1,0:T(8,128)} all-reduce(f32[768,768] %x)"
    assert tr.op_label(a) == "fusion (bf16[3072], bf16[128,128,3072])"
    assert tr.op_label(c) == "all-reduce f32[768,768]"
    assert tr.op_label("plain name") == "plain name"
    ops = [(a, 0, 2 * MS), (b, 2 * MS, 2 * MS), (c, 4 * MS, 1 * MS)]
    assert tr.top_operations(ops, 5) == [
        ["fusion (bf16[3072], bf16[128,128,3072])", 0.004],
        ["all-reduce f32[768,768]", 0.001]]
