"""The decode lane's feed contract (serving/lane.py, PR 47): a decode step
and a prefill chunk each take ONE packed int32 feed, laid out by
``decode_layout`` / ``prefill_layout``, packed by ``decode_feed`` /
``prefill_feed`` and sliced apart inside the program into the variables
``decoder(frame)`` is handed.  Held here over the five forms a lane's
declaration takes (one cache kind; two; per-sequence state; state beside
latent rows; an image encoder's index) by a decoder that computes
nothing, so that the program's own variables can be fetched; and on tiny
engines, by the executor's counter: one host array a run of either
served program, nothing compiled after warm-up.  Since PR 48 the chunk's
feed holds ``pf_final``, the condition the chunk's head runs under: with
1 the chunk's token is the whole-sequence form's argmax at the chunk's
last row, with 0 it is 0 and pools and state are written as with 1."""

import numpy as np
import pytest

from paddle_tpu import fluid, serving
from paddle_tpu import observability as obs
from paddle_tpu.fluid import layers as L
from paddle_tpu.serving import lane

from test_lane_hlo_unchanged import _later_model, _zero_scope

SLOTS, PAGE, CHUNK, MAX_PAGES, NUM_PAGES, IMAGE_ROWS = 3, 4, 8, 6, 9, 24
STATE = [lane.SeqState("s", (2, 3), "float32")]
ENCODER = dict(build=None, prepare=None, shapes=[(4, 4)],
               rows_of=lambda shape: 16, row_width=8, placeholder_id=1)
# form -> the declaration's keywords beside the decoder and the head
FORMS = {
    "one_kind": dict(cache_rows=lambda dt: lane.kv_rows(2, 4, dt)),
    "two_kinds": dict(cache_rows=lambda dt: lane.kv_rows(2, 4, dt),
                      layer_windows=[None, 8]),
    "state": dict(cache_rows=lambda dt: lane.kv_rows(2, 4, dt),
                  seq_state=STATE, state_layers=[1]),
    "state_latent_rows": dict(
        cache_rows=lambda dt: [lane.CacheRow("latent", 128, dt)],
        seq_state=STATE, state_layers=[0, 2]),
    "encoder_index": dict(
        cache_rows=lambda dt: [lane.CacheRow("latent", 128, dt)],
        encoder=lane.ImageEncoder(**ENCODER)),
}


def _built(form, which):
    """(program, layout, the Frame its decoder was handed) of one form's
    decode step or prefill chunk around a decoder that computes nothing."""
    frames = []

    def decoder(frame):
        frames.append(frame)
        return L.cast(L.unsqueeze(frame.tok, [2]), "float32")

    def head(h):
        return (L.cast(L.reshape(h, shape=[-1]), "int64"),
                L.reshape(h, shape=[-1, 1]))

    decl = lane.scaffold(decoder, head, num_layers=2, max_position=64,
                         **FORMS[form])
    pages = ({k: NUM_PAGES for k in ("full", "window8")}
             if decl.layer_windows else NUM_PAGES)
    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start), fluid.unique_name.guard():
        if which == "decode":
            layout, _, _ = decl.build_decode_step(SLOTS, pages, PAGE,
                                                  MAX_PAGES)
        else:
            layout, _, _ = decl.build_prefill_chunk(
                CHUNK, pages, PAGE, MAX_PAGES,
                **({"image_rows": IMAGE_ROWS} if decl.encoder else {}))
    return decl, main, layout, frames[0]


def _arguments(decl, which, rng):
    """What the engine hands ``decode_feed`` / ``prefill_feed`` for this
    declaration, in the dtypes it makes them, and {piece: value}."""
    kinds = ["full", "window8"] if decl.layer_windows else ["full"]
    rows, head = (SLOTS, "dec") if which == "decode" else (1, "pf")
    wide = (SLOTS, 1) if which == "decode" else (1, CHUNK)
    tok = rng.randint(0, 50000, wide).astype(np.int64)
    pos = rng.randint(0, 40000, wide).astype(np.int64)
    tables = {k: rng.randint(0, NUM_PAGES, (rows, MAX_PAGES)).astype(np.int32)
              for k in kinds}
    n_write = SLOTS if which == "decode" else CHUNK // PAGE
    writes = {k: rng.randint(0, NUM_PAGES, n_write).astype(np.int32)
              for k in kinds}
    block = (rng.randint(0, 5, rows).astype(np.int32) if decl.seq_state
             else None)
    want = {f"{head}_tok": tok, f"{head}_pos": pos}
    for k in kinds:
        want[lane.kind_feed(f"{head}_page_table", k)] = tables[k]
    if which == "decode":
        off = rng.randint(0, PAGE, SLOTS).astype(np.int32)
        args = [tok, pos, tables, writes, off, block]
        want["dec_write_off"] = off
        for k in kinds:
            want[lane.kind_feed("dec_write_page", k)] = writes[k]
    else:
        q_start = rng.randint(0, 40000, 1).astype(np.int32)
        last = rng.randint(0, CHUNK, 1).astype(np.int64)
        final = rng.randint(0, 2, 1).astype(np.int32)
        row_idx = None
        if decl.encoder is not None:
            row_idx = (decl.encoder.index_feed,
                       rng.randint(-1, IMAGE_ROWS, (1, CHUNK)).astype(np.int32))
            want[row_idx[0]] = row_idx[1]
        args = [tok, pos, tables, writes, q_start, last, final, block,
                row_idx]
        want.update(pf_qstart=q_start, pf_last_idx=last, pf_final=final)
        for k in kinds:
            want[lane.kind_feed("pf_write_pages", k)] = writes[k]
    if block is not None:
        want[lane.STATE_FEEDS[
            "decode" if which == "decode" else "prefill"]] = block
    return args, want


@pytest.mark.parametrize("which", ["decode", "prefill"])
@pytest.mark.parametrize("form", list(FORMS))
def test_a_served_program_takes_one_packed_feed(form, which):
    decl, main, layout, frame = _built(form, which)
    fill = lane.decode_feed if which == "decode" else lane.prefill_feed
    derive = (lane.decode_layout if which == "decode"
              else lane.prefill_layout)
    args, want = _arguments(decl, which, np.random.RandomState(47))
    derived = derive.cache_info().misses
    feed = fill(*args)

    # (a) one entry, int32, as long as its pieces together
    assert list(feed) == [layout.feed] == [
        v.name for v in main.global_block().vars.values()
        if getattr(v, "is_data", False)]
    buf = feed[layout.feed]
    assert buf.dtype == np.int32 and buf.shape == (layout.size,)
    assert layout.size == sum(v.size for v in want.values())

    # (d) the filler packed by the very layout the builder declared from
    assert derive.cache_info().misses == derived
    kinds = tuple(args[2])
    state = decl.seq_state != []
    assert layout is (
        lane.decode_layout(kinds, SLOTS, MAX_PAGES, state)
        if which == "decode" else lane.prefill_layout(
            kinds, CHUNK, CHUNK // PAGE, MAX_PAGES, state,
            decl.encoder.index_feed if decl.encoder else None))

    # (b) every piece, read back from the buffer at the declared layout
    # and fetched from the program as the variable the decoder is handed,
    # is what was passed, in the program's shape and dtype
    assert list(layout.pieces) == list(layout.unpack(feed)) and set(
        layout.pieces) == set(want)
    handed = {"tok": frame.tok, "pos": frame.pos,
              "last_idx": frame.last_idx, "state_block": frame.state_block}
    for kind, table in frame.tables.items():
        handed[f"table.{kind}"] = table
    if which == "prefill":
        handed["q_start"] = frame.q_start
    if frame.image_rows is not None:
        handed["row_idx"] = frame.image_rows[1]
    head = "dec" if which == "decode" else "pf"
    piece_of = {"tok": f"{head}_tok", "pos": f"{head}_pos",
                "last_idx": "pf_last_idx", "q_start": "pf_qstart",
                "state_block": lane.STATE_FEEDS[which],
                "row_idx": "pf_row_idx"}
    for kind in frame.tables:
        piece_of[f"table.{kind}"] = lane.kind_feed(f"{head}_page_table",
                                                   kind)
    handed = {piece_of[k]: v for k, v in handed.items() if v is not None}
    # the page writers hold the write pieces, the frame's conditional
    # around the head the chunk's pf_final
    assert set(want) - set(handed) <= {
        n for n in want if "write" in n or n == "pf_final"}
    fetched = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=feed, fetch_list=[v.name for v in handed.values()],
        scope=fluid.Scope())
    read = layout.unpack(feed)
    for name, value in want.items():
        _, shape, dtype = layout.pieces[name]
        assert read[name].shape == shape == value.shape
        assert read[name].dtype == np.dtype(dtype) == value.dtype
        np.testing.assert_array_equal(read[name], value)
    for (name, var), got in zip(handed.items(), fetched):
        assert tuple(var.shape) == layout.pieces[name].shape
        # (an int64 a program computes is int32 to jax without x64, as
        # the int64 feeds of before were once traced)
        assert var.dtype in (layout.pieces[name].dtype, "int32")
        np.testing.assert_array_equal(np.asarray(got), want[name])

    # (c) a value past int32 raises with the piece's name
    for slot, piece in ((0, f"{head}_tok"), (1, f"{head}_pos")):
        for past in (2 ** 31, -2 ** 31 - 1):
            broken = list(args)
            broken[slot] = args[slot].copy()
            broken[slot].flat[-1] = past
            with pytest.raises(OverflowError, match=f"'{piece}'"):
                fill(*broken)
    broken = list(args)
    table = args[2][kinds[-1]].astype(np.int64)
    table[0, 0] = 2 ** 40
    broken[2] = dict(args[2], **{kinds[-1]: table})
    with pytest.raises(OverflowError, match="page_table"):
        fill(*broken)
    # and a piece of another shape than the program's is refused by name
    broken = list(args)
    broken[0] = args[0][:, :-1] if which == "prefill" else args[0][:-1]
    with pytest.raises((ValueError, KeyError)):
        fill(*broken)


def _staged(kind):
    fam = obs.REGISTRY.snapshot().get("pt_exec_staged_arrays_total", {})
    return (fam.get("samples") or {}).get(("single", kind), 0)


def _compile_misses():
    fam = obs.REGISTRY.snapshot().get("pt_compile_cache_total", {})
    return sum(v for k, v in (fam.get("samples") or {}).items()
               if k[0] == "single" and k[1] != "hit")


@pytest.mark.parametrize("model", ["gpt", "trinity", "olmo_hybrid"])
def test_one_host_array_a_run_and_nothing_compiles_after_warmup(model):
    """gpt feeds 5 pieces a step, trinity 7 (two cache kinds),
    olmo_hybrid 6 (state): each run of either served program stages ONE
    host array, and the scope's arrays as it did."""
    from paddle_tpu.models import gpt

    if model == "gpt":
        cfg = gpt.GPTConfig.tiny()
        builds = [lambda: gpt.build_gpt_lm(cfg, is_test=True)]
    else:
        cfg, builds = _later_model(model)
    eng = serving.DecodeEngine(
        cfg, scope=_zero_scope(*builds), place=fluid.CPUPlace(),
        pool_slots=3, page_size=4, prefill_chunk=8, max_len=32,
        auto_start=False, name=f"one-feed-{model}")
    try:
        eng.warmup()
        assert len(eng._dec_layout.pieces) == {
            "gpt": 5, "trinity": 7, "olmo_hybrid": 6}[model]
        misses = _compile_misses()
        for run in (lambda: eng._run_decode_feed([], warm=True),
                    lambda: eng._run_prefill_feed(
                        **eng._warm_prefill_args(), warm=True)):
            run()   # what the scope holds on the device is kept from here
            before = {k: _staged(k) for k in ("host", "put", "any", "kept")}
            run()
            gained = {k: _staged(k) - n for k, n in before.items()}
            # every argument but the one feed is the scope's (kept, or put
            # where this scope holds numpy)
            assert gained["host"] == 1
            assert gained["any"] - gained["kept"] - gained["put"] == 1
        # a window of turns: two requests of more than one chunk each
        reqs = [eng.submit_request(list(range(1, n)), 5) for n in (12, 19)]
        runs = []
        for name in ("_run_decode_feed", "_run_prefill_feed"):
            def counted(*args, _name=name, _inner=getattr(eng, name), **kw):
                runs.append(_name)
                return _inner(*args, **kw)
            setattr(eng, name, counted)
        host = _staged("host")
        while not all(r.future.done() for r in reqs):
            eng._step_once()
        assert len(set(runs)) == 2 and len(runs) > 8
        assert _compile_misses() == misses
        assert _staged("host") - host == len(runs)
        assert [len(r.future.result()) for r in reqs] == [5, 5]
    finally:
        eng.close()


def _snapshot(eng):
    """{name: value} of every pool and state tensor of the engine."""
    return {name: np.asarray(eng.scope.get(name)).copy()
            for names in (*eng.pool.var_names, *eng.pool.state_var_names)
            for name in names}


def _restore(eng, held):
    import jax.numpy as jnp

    for name, value in held.items():
        eng.scope.set(name, jnp.asarray(value))


@pytest.mark.parametrize("model", ["gpt", "trinity", "olmo_hybrid"])
def test_the_head_runs_where_pf_final_says_and_nothing_else_moves(model):
    """A prompt of two chunks (8 + 5 tokens): the engine feeds 0 on the
    first chunk and 1 on the last.  Either chunk fed 1 returns the
    whole-sequence form's argmax at its last row; fed 0 its program returns
    0 and leaves every pool and state tensor bit-equal to the run fed 1."""
    from paddle_tpu.models import gpt

    if model == "gpt":
        cfg = gpt.GPTConfig.tiny()
        builds = [lambda: gpt.build_gpt_lm(cfg, is_test=True)]
    else:
        cfg, builds = _later_model(model)
    rng = np.random.RandomState(48)
    eng = serving.DecodeEngine(
        cfg, scope=_zero_scope(*builds, rng=rng), place=fluid.CPUPlace(),
        pool_slots=3, page_size=4, prefill_chunk=8, max_len=32,
        auto_start=False, name=f"final-{model}")
    try:
        prompt = rng.randint(1, cfg.vocab_size, 13).tolist()
        whole, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(whole, start), fluid.unique_name.guard():
            logp = eng.lane.build_whole_sequence(16, page_size=4)
        tok = np.zeros((1, 16), np.int64)
        tok[0, :13] = prompt
        (logp,) = fluid.Executor(fluid.CPUPlace()).run(
            whole, feed={"pf_tok": tok,
                         "pf_pos": np.arange(16, dtype=np.int64)[None]},
            fetch_list=[logp.name], scope=eng.scope)
        want = np.asarray(logp).argmax(-1)
        assert want[7] != 0 and want[12] != 0   # 0 is what a skipped head reads

        eng.warmup()
        fed, inner = [], eng._prefill_feed

        def recorded(*args, **kw):
            fed.append(inner(*args, **kw))
            return fed[-1]

        eng._prefill_feed = recorded
        toks, run = [], eng._run_prefill_feed

        def ran(**kw):
            tok = run(**kw)
            # a chunk fed 0 is not waited for and hands back None (PR 50):
            # what its program returned is the output the engine keeps
            toks.append(tok if tok is not None else int(
                np.asarray(eng._in_flight[-1][1]).reshape(-1)[0]))
            return tok

        eng._run_prefill_feed = ran
        final = eng._pf_layout.pieces["pf_final"].offset
        req = eng.submit_request(prompt, 1)
        for chunk, row in enumerate((7, 12)):
            before = _snapshot(eng)
            eng._prefill_one_chunk()
            (name, packed), = fed[chunk].items()
            flag = bool(packed[final])
            assert flag == (chunk == 1)
            got = {flag: toks[chunk]}
            after = {flag: _snapshot(eng)}
            # the same feed over the same pool with the flag turned (the
            # engine has given window pages back since: its tables moved)
            _restore(eng, before)
            turned = packed.copy()
            turned[final] = not flag
            (tok,) = eng._exe.run(eng._pf_prog, feed={name: turned},
                                  fetch_list=[eng._pf_fetch], scope=eng.scope)
            got[not flag] = int(np.asarray(tok).reshape(-1)[0])
            after[not flag] = _snapshot(eng)
            assert got == {True: want[row], False: 0}
            assert any(np.any(v != before[k]) for k, v in after[True].items())
            for key, value in after[True].items():
                np.testing.assert_array_equal(value, after[False][key], key)
            _restore(eng, after[True])
        assert req.generated == [want[12]] and len(fed) == 2
    finally:
        eng.close()
