"""The decode-lane declaration: what a model file hands
``serving.DecodeEngine`` so that one engine, one scheduler and one
``KVPool`` serve it (docs/SERVING.md "Decode-lane declaration").

A model declares

- the **cache rows** a token leaves in each layer: a list of named row
  tensors of any width and dtype (``CacheRow``).  Dense multi-head
  attention leaves a K and a V row (``kv_rows``); latent attention with
  a learned indexer leaves a latent row and an indexer key.  The pool
  allocates every row tensor ``[num_pages, page_size, width]``.  A lane
  whose layers of different kinds leave rows of different widths (a
  window layer with more K/V heads than a full one) declares its rows BY
  KIND, ``{kind: [CacheRow]}``: pool tensors, the bytes of a page and
  the modeled bytes then follow each layer's kind, as the pages do;
- optionally the **kind** of cache each layer leaves (``layer_windows``):
  ``full`` (a token's rows stay until its request ends) or ``window`` with
  its W (a layer that attends the last W tokens: rows wholly below a
  sequence's window are given back while the request lives).  The pool
  keeps ONE page list a kind a sequence, so every layer of a kind reads
  through the same page table; a model that declares nothing has the one
  kind ``full`` and the feed pieces it always had;
- optionally the **state a sequence owns** in some layers
  (``seq_state``, ``state_layers``): named tensors of any shape and dtype
  (``SeqState``) that every token of the sequence overwrites, not rows a
  token leaves: a linear-attention layer's recurrent state, a short
  convolution's last inputs.  The pool allocates every state tensor
  ``[blocks, *shape]`` for each of ``state_layers`` and gives a sequence
  exactly ONE block, whatever its length, from the time it is opened
  until it ends or is evicted (the cache kind ``state``, beside the page
  kinds).  The programs find a sequence's block by its index, one piece
  more in each executable's feed (``dec_state_block`` [slots],
  ``pf_state_block`` [1]; inactive slots and warm-up name the trash
  block 0), and read it as zeros in a sequence's first chunk
  (``pf_qstart == 0``): nothing clears a block on the host, and an
  eviction replays from token 0.  A lane that declares no state builds,
  feeds and compiles exactly what it did;
- its **decoder and its head** (``scaffold``): ``decoder(frame)`` runs
  the embedding and every block over the tokens of a ``Frame`` and
  returns the hidden state ``[B, T, D]``, ``head(h)`` turns rows of it
  into ``(greedy next token, logprobs)``, both against the model's own
  parameter names.  The lane's two fixed-shape executables, a decode
  step over the pool's slots and a prefill chunk of one sequence, are
  built around them HERE (below);
- optionally an **image encoder** (``ImageEncoder``): a third builder,
  one executable an image shape, whose output rows stand at the prompt
  positions that hold the model's placeholder id.  A request may then
  carry images; the scheduler runs the encoder inside its turn loop, one
  image at most a turn, just ahead of the first prefill chunk that needs
  the image's rows.  The rows never leave the device: an encoder run
  writes them into the engine's **row staging** var (``ROW_STAGING``, one
  for the engine, as large as the largest declared image and one chunk),
  the chunk reads them from there by an index a position (one piece more
  in the chunk's feed), and a place is written over once the chunk that
  held its
  position has run.  An eviction replays from token 0 and encodes again.
  A lane that declares no encoder builds, feeds and compiles exactly
  what it did before lanes could.

The model's config class returns the declaration from ``decode_lane()``;
the engine asks for nothing else, so ``serving/decode.py`` imports no
model module.

What a model file holds, what the lane builds.  A model file holds its
config class with ``decode_lane()``, its attention (linear-attention,
tower) block, its feed-forward block, its ``_decoder(frame, cfg)`` and
its head (``models/decode_blocks.py`` has the parts the decoders share).
Everything that is the ENGINE's is here: the feed contract (below), the
pool, state and staging vars, the page
writers of each cache kind, ``q_start``, ``row_valid``, the chunk's
last-valid-row gather, and the whole-sequence form
(``build_whole_sequence``: identity page table, caches and state that
live and die inside the program) that a model's ``build_<model>_lm`` is
a few lines over.  A lane of one cache kind and a lane of several go
through the same code: one kind is the dict of one.  A new serve
configuration touches its model file, its kernel
(``kernels/primitives``) and its declaration, and nothing here.

The feed contract: ONE host transfer a program.  A decode step and a
prefill chunk each declare one feed, ``dec_feed`` / ``pf_feed``: int32,
1-D, of a length static in ``pool_slots``, ``max_pages``, the chunk
length, the lane's cache kinds, ``seq_state`` and the encoder's index.
Every scheduling table the scheduler builds afresh each turn (token ids,
positions, a page table and the write pages a cache kind, offsets, state
blocks, the staged-row index: all small integers) is a PIECE of it.
The LAYOUT (``decode_layout`` / ``prefill_layout``: piece -> offset,
shape, dtype) is the contract: the builder declares the one feed and
slices, shapes and casts it apart into the variables ``decoder(frame)``
and ``head`` are handed (``_declare_feed``), the filler (``decode_feed`` /
``prefill_feed``) writes each piece into its slice of one ``numpy``
buffer, and both are handed the same cached layout object, derived from
the arguments the builder receives, so the two cannot drift.  A host
array that rides a jitted call costs 0.13-0.16 ms of the call (PERF.md
§6, PR 34), so five to eight feeds a program were 0.5-0.9 ms of every
run that one buffer does not pay.  No other module spells a feed's or a
piece's name; a test or a reader asks the layout
(``FeedLayout.unpack``).  The image encoder's own program (patches:
float, megabytes) and the whole-sequence form keep their feeds by name.
"""

from __future__ import annotations

import collections
import functools
import math
import types

import numpy as np

__all__ = ["CacheRow", "SeqState", "STATE", "STATE_FEEDS",
           "state_var_names", "declare_state_vars",
           "DecodeLane", "DeviceCounter", "POOL_PREFIX",
           "ImageEncoder", "PreparedImage", "ROW_STAGING",
           "declare_row_staging",
           "FULL", "kind_name", "kind_feed", "kinds_of",
           "window_pages_per_seq", "rows_of_layers",
           "kv_rows", "lane_padded", "pool_var_names", "declare_pool_vars",
           "Frame", "scaffold", "Piece", "FeedLayout", "decode_layout",
           "prefill_layout", "decode_feed", "prefill_feed",
           "build_decode_step", "build_prefill_chunk",
           "build_whole_sequence"]

POOL_PREFIX = "@KVPOOL@"

# one row tensor of the cache: ``width`` values of ``dtype`` a token a layer
CacheRow = collections.namedtuple("CacheRow", ("name", "width", "dtype"))
# one tensor of the state a sequence owns in a layer: ``shape`` values of
# ``dtype`` a sequence (a block), overwritten by every token
SeqState = collections.namedtuple("SeqState", ("name", "shape", "dtype"))
# an int32 vector of ``length`` counts that the lane's programs add to in
# place, under the persistable var ``name``
DeviceCounter = collections.namedtuple("DeviceCounter", ("name", "length"))


# one image as its encoder takes it: ``shape`` names the executable (a
# patch grid), ``feeds`` are the encoder's input arrays, ``rows`` the
# prompt positions its output fills
PreparedImage = collections.namedtuple("PreparedImage",
                                       ("shape", "feeds", "rows"))
# the engine's staged image rows, float32 [rows, 1, row_width]
ROW_STAGING = "@IMGROWS@"


FULL = "full"
# the cache kind of per-sequence state: one block a sequence
STATE = "state"
# the piece of the packed feed that names each slot's state block (decode
# step) and the prefilling sequence's (prefill chunk)
STATE_FEEDS = {"decode": "dec_state_block", "prefill": "pf_state_block"}


def kind_name(window):
    """The name of a layer's cache kind: ``full`` (``window`` None), or
    ``window<W>`` for a layer that attends the last W tokens."""
    return FULL if window is None else f"window{int(window)}"


def kinds_of(layer_windows):
    """The distinct windows of ``layer_windows`` in the order the pool
    and the feeds keep their kinds: ``full`` (None) first, then the
    windows by size."""
    return sorted(set(layer_windows), key=lambda w: (w is not None, w))


def kind_feed(feed, kind):
    """The piece of the packed feed that carries ``feed`` (a page table,
    a write page) for cache kind ``kind``: the name itself for ``full``,
    ``<feed>@<kind>`` for a window kind."""
    return feed if kind == FULL else f"{feed}@{kind}"


def window_pages_per_seq(window, chunk, page_size):
    """The most pages one sequence holds in a window kind: the W keys
    the chunk's first query sees and the chunk itself, in whole pages,
    and one more for a window that starts inside a page."""
    return -(-(int(window) + int(chunk)) // int(page_size)) + 1


def lane_padded(width):
    """``width`` rounded up to whole 128-lane tiles: the width to STORE a
    cache row at.  XLA:TPU lays ``[pages, page, 576]`` out with the page
    index minor-most (576 would pad to 640 lanes anyway), and every
    executable then copies the whole pool into the row-major layout its
    kernels read and back (PERF.md finding 4; asked of the compiler
    chip-free, PR 27: ten whole-pool copies and 6.1 GB of temporaries in
    one decode step).  Stored 640 wide the default layout is row-major
    and nothing is copied; the pad lanes hold zeros."""
    return -(-int(width) // 128) * 128


def kv_rows(num_heads, head_dim, dtype="float32"):
    """The rows dense multi-head attention leaves: K and V, the heads
    side by side (kernels/primitives/paged.py "Shapes").  ``int8`` is the
    dual-int8 pool (docs/KERNELS.md "int8 KV"): hi and lo int8 rows plus
    one float32 scale a head, for K and for V."""
    width = int(num_heads) * int(head_dim)
    if dtype == "int8":
        return [CacheRow(f"{kv}__{part}", w, dt) for kv in ("k", "v")
                for part, w, dt in (("qhi", width, "int8"),
                                    ("qlo", width, "int8"),
                                    ("scale", int(num_heads), "float32"))]
    return [CacheRow("k", width, dtype), CacheRow("v", width, dtype)]


def rows_of_layers(rows, num_layers, layer_windows=None):
    """Per layer, the rows a token leaves there: ``rows`` itself for a
    lane that declares one list, its kind's list for a lane that
    declares ``{kind: [CacheRow]}``."""
    if not isinstance(rows, dict):
        return [list(rows)] * int(num_layers)
    windows = ([None] * int(num_layers) if layer_windows is None
               else layer_windows)
    kinds = [kind_name(w) for w in windows]
    if set(kinds) != set(rows):
        raise ValueError(
            f"cache rows declared for kinds {sorted(rows)}, but the "
            f"layers are of kinds {sorted(set(kinds))}")
    return [list(rows[k]) for k in kinds]


def pool_var_names(rows, num_layers, prefix=POOL_PREFIX, layer_windows=None):
    """Per layer, the pool var name of each row declared for it, in
    order."""
    return [tuple(f"{prefix}{row.name}_l{i}" for row in layer_rows)
            for i, layer_rows in enumerate(
                rows_of_layers(rows, num_layers, layer_windows))]


def declare_pool_vars(rows, num_layers, num_pages, page_size,
                      prefix=POOL_PREFIX, layer_windows=None):
    """The pool's persistable vars in the program being built: per layer
    one ``[num_pages, page_size, width]`` var a declared row.  That shape
    keeps the default row-major TPU layout for any width of 128 or more
    (and the kernels read it as stored), so no executable copies a pool
    tensor (PERF.md finding 4).  With ``layer_windows`` (a lane of more
    than one cache kind) ``num_pages`` is ``{kind: pages}`` and layer i's
    vars have its kind's; ``rows`` declared by kind give layer i its
    kind's rows too."""
    from paddle_tpu import fluid

    block = fluid.default_main_program().global_block()

    def pages(layer):
        if layer_windows is None:
            return int(num_pages)
        return int(num_pages[kind_name(layer_windows[layer])])

    return [tuple(block.create_var(
        name=name, shape=[pages(layer), int(page_size), row.width],
        dtype=row.dtype, persistable=True)
        for name, row in zip(names, layer_rows))
        for layer, (names, layer_rows) in enumerate(zip(
            pool_var_names(rows, num_layers, prefix, layer_windows),
            rows_of_layers(rows, num_layers, layer_windows)))]


def state_var_names(states, state_layers, prefix=POOL_PREFIX):
    """Per state layer (the model's own layer numbers), the pool var
    name of each declared state tensor, in order."""
    return [tuple(f"{prefix}{st.name}_l{int(layer)}" for st in states)
            for layer in state_layers]


def declare_state_vars(states, state_layers, num_blocks,
                       prefix=POOL_PREFIX):
    """The pool's state vars in the program being built: per state layer
    one ``[num_blocks, *shape]`` persistable var a declared state tensor
    (block 0 the trash block).  Returns {layer: (var, ...)}."""
    from paddle_tpu import fluid

    block = fluid.default_main_program().global_block()
    return {int(layer): tuple(block.create_var(
        name=name, shape=[int(num_blocks), *map(int, st.shape)],
        dtype=st.dtype, persistable=True)
        for name, st in zip(names, states))
        for layer, names in zip(
            state_layers, state_var_names(states, state_layers, prefix))}


def declare_row_staging(rows, width, name=ROW_STAGING):
    """The engine's staged image rows in the program being built:
    persistable float32 ``[rows, 1, width]``, a page of one row each, so
    that an encoder writes it with ``kv_cache_write`` (in place, as the
    pool is written) and a chunk reads it with
    ``select_embedding_rows``."""
    from paddle_tpu import fluid

    return fluid.default_main_program().global_block().create_var(
        name=name, shape=[int(rows), 1, int(width)], dtype="float32",
        persistable=True)


class ImageEncoder:
    """A lane's image encoder (a vision tower and its projector).

    ``build(grid_h, grid_w, staging_rows, attn_force=)`` builds ONE
    image shape's program into the default main program and returns
    ``(feed names, prepare program or None)``: the program writes the
    image's ``rows_of(shape)`` output rows, ``row_width`` wide, into the
    row staging var (``declare_row_staging(staging_rows, row_width)``)
    at the places its ``places_feed`` [rows] int32 names; nothing of it
    is fetched.  The prepare program, where there is one, is run once
    when the engine builds the shape (a position table resized to the
    shape, say).
    ``prepare(image)`` -> ``PreparedImage``: a caller's image as the
    encoder's input feeds (host work, done as the request is submitted).
    ``shapes``: the shapes ``DecodeEngine.warmup`` compiles; another
    shape, of no more rows than the largest of these, compiles when its
    first image arrives.
    ``placeholder_id``: the prompt token whose positions image rows
    fill, in order.  ``index_feed``: the piece [1, C] int32 of the
    prefill chunk's feed that names, a position, the staged row standing
    there, or -1 for a token (the chunk builder is handed ``image_rows=``
    the staging var's rows and lays the piece out)."""

    def __init__(self, *, build, prepare, shapes, rows_of, row_width,
                 placeholder_id, index_feed="pf_row_idx",
                 places_feed="enc_row_idx"):
        self.build = build
        self.prepare = prepare
        self.shapes = [tuple(s) for s in shapes]
        self.rows_of = rows_of
        self.row_width = int(row_width)
        self.placeholder_id = int(placeholder_id)
        self.index_feed = index_feed
        self.places_feed = places_feed
        if not self.shapes:
            raise ValueError("ImageEncoder: declare the image shapes to "
                             "compile at warm-up (the largest sizes the "
                             "row staging)")


class DecodeLane:
    """A model's decode-lane declaration.

    ``cache_rows(pool_dtype)`` -> [CacheRow]: what a token leaves in each
    of ``num_layers`` layers at that storage dtype (raise for a dtype the
    model has no kernels for); or ``{kind: [CacheRow]}`` where the layers
    of each cache kind (``layer_windows``) leave rows of their own.
    ``num_layers`` counts the layers that
    leave cache rows: a model whose other layers hold per-sequence state
    instead numbers its cache layers 0 .. ``num_layers`` - 1 itself.
    ``seq_state``: ``[SeqState]`` the state a sequence owns in each of
    ``state_layers`` (the model's own layer numbers); the builders are
    then handed ``state_blocks=`` (the blocks of every state tensor,
    trash included) and take the state-block piece (``STATE_FEEDS``).
    ``build_decode_step(pool_slots, num_pages, page_size, max_pages,
    pool_dtype=, attn_force=)`` and ``build_prefill_chunk(chunk_len,
    num_pages, page_size, max_pages, pool_dtype=, attn_force=)`` build
    into the default main program and return ``(the feed's layout,
    next_tok, logprobs)``: this module's builders of those names bound to the
    model's decoder and head, which is what ``scaffold`` returns a
    declaration with (and with ``build_whole_sequence``, None where the
    builders are a model's own).
    ``pool_dtype`` / ``prefill_chunk``: the model's defaults where the
    engine is given none.
    ``layer_windows``: per layer, None (kind ``full``) or the W of a layer
    that attends the last W tokens only (kind ``window<W>``).  Left out,
    every layer is ``full``.  Declared, the pool keeps one page list a
    kind a sequence and sizes each kind's tensors by that kind's worst
    case; the builders are handed ``num_pages`` as ``{kind: pages}`` and
    lay out one page table and one set of write pages a kind in their
    feed (``kind_feed``: ``dec_page_table`` for ``full``,
    ``dec_page_table@window<W>`` for a window kind).
    ``device_counters``: ``[DeviceCounter]`` the programs keep on the
    device: persistable int32 vectors they add to in place and no step
    fetches.  The engine installs them as zeros beside the pool and
    reads them only when ``DecodeEngine.book_device_counters()`` is
    called (a trace reader, a test), handing what each gained to ``book_counters(engine_name, {name: gained})``,
    the model's own mapping onto metric families.  What they count is
    the model's business: the engine knows their names and lengths.
    ``encoder``: an ``ImageEncoder``, or None (no request carries images
    and nothing is built, fed or compiled for them)."""

    def __init__(self, *, num_layers, max_position, cache_rows,
                 build_decode_step, build_prefill_chunk,
                 pool_dtype="float32", prefill_chunk=None,
                 device_counters=(), book_counters=None,
                 layer_windows=None, encoder=None, seq_state=(),
                 state_layers=()):
        self.num_layers = int(num_layers)
        self.max_position = int(max_position)
        self.cache_rows = cache_rows
        self.build_decode_step = build_decode_step
        self.build_prefill_chunk = build_prefill_chunk
        self.build_whole_sequence = None
        self.pool_dtype = pool_dtype
        self.prefill_chunk = prefill_chunk
        self.device_counters = list(device_counters)
        self.book_counters = book_counters
        self.encoder = encoder
        self.seq_state = list(seq_state)
        self.state_layers = [int(n) for n in state_layers]
        if bool(self.seq_state) != bool(self.state_layers):
            raise ValueError(
                "DecodeLane: seq_state and state_layers go together (the "
                "state tensors, and the layers that own them)")
        self.layer_windows = (None if layer_windows is None
                              else list(layer_windows))
        if (self.layer_windows is not None
                and len(self.layer_windows) != self.num_layers):
            raise ValueError(
                f"DecodeLane: layer_windows names {len(self.layer_windows)} "
                f"layers of {self.num_layers}")
        if self.device_counters and book_counters is None:
            raise ValueError("DecodeLane: device_counters without "
                             "book_counters would never be read")


# ---------------------------------------------------------------------------
# The feed contract and the programs' frame: what the engine feeds each
# served program (ONE packed int32 buffer, and where each piece lies in
# it), and the part of the lane's programs that is the same for every
# model.  No other module spells a feed's or a piece's name.
# ---------------------------------------------------------------------------

_DEC_FEED, _PF_FEED = "dec_feed", "pf_feed"
_DEC_TOK, _DEC_POS, _DEC_WRITE_OFF = "dec_tok", "dec_pos", "dec_write_off"
_DEC_TABLE, _DEC_WRITE_PAGE = "dec_page_table", "dec_write_page"
_PF_TOK, _PF_POS, _PF_QSTART = "pf_tok", "pf_pos", "pf_qstart"
_PF_TABLE, _PF_WRITE_PAGES = "pf_page_table", "pf_write_pages"
_PF_LAST_IDX, _PF_FINAL = "pf_last_idx", "pf_final"

_INT32 = np.iinfo(np.int32)

# one piece of a packed feed: ``shape`` values from ``offset`` on, which
# the program reads as ``dtype``
Piece = collections.namedtuple("Piece", ("offset", "shape", "dtype"))


class FeedLayout(collections.namedtuple("FeedLayout",
                                        ("feed", "pieces", "size"))):
    """A served program's one feed: ``feed`` is its name, int32
    [``size``]; ``pieces`` {name: ``Piece``} says where each scheduling
    table lies in it, in the order they are packed, and the shape and
    dtype of the variable the program slices it into.  The builder
    declares from it (``_declare_feed``), the filler packs by it (``pack``),
    and both take it from the same cached call (``decode_layout`` /
    ``prefill_layout``), so the two cannot drift."""
    __slots__ = ()

    def pack(self, values):
        """``values`` {piece: integers of the piece's shape} as the
        one-entry feed.  Every value must fit int32 (a token id, a
        position, a page or block index, an offset or -1 all do): one
        that does not raises with the piece's name."""
        buf = np.empty(self.size, np.int32)
        for name, (offset, shape, _) in self.pieces.items():
            val = np.asarray(values[name])
            if val.shape != shape:
                raise ValueError(
                    f"feed piece {name!r}: shape {val.shape}, the program "
                    f"was built for {shape}")
            if val.dtype != np.int32 and val.size and not (
                    _INT32.min <= val.min() and val.max() <= _INT32.max):
                raise OverflowError(
                    f"feed piece {name!r} holds a value past int32 "
                    f"(min {val.min()}, max {val.max()}): the packed feed "
                    f"{self.feed!r} carries int32")
            buf[offset:offset + val.size] = val.reshape(-1)
        return {self.feed: buf}

    def unpack(self, feed):
        """{piece: its values} read back from a packed ``feed`` (what
        ``pack`` returned), each in the shape and dtype of the program's
        variable: what a test or a reader asks in place of a feed's
        name."""
        buf = feed[self.feed]
        return {name: buf[offset:offset + math.prod(shape)]
                .reshape(shape).astype(dtype)
                for name, (offset, shape, dtype) in self.pieces.items()}


def _layout(feed, pieces):
    """``pieces`` [(name, shape, dtype)] laid end to end."""
    laid, offset = {}, 0
    for name, shape, dtype in pieces:
        shape = tuple(int(n) for n in shape)
        laid[name] = Piece(offset, shape, dtype)
        offset += math.prod(shape)
    # read-only: the cache hands every caller the same object
    return FeedLayout(feed, types.MappingProxyType(laid), offset)


@functools.lru_cache(maxsize=None)
def decode_layout(kinds, pool_slots, max_pages, state):
    """The layout of a decode step's feed ``dec_feed``: ``dec_tok`` /
    ``dec_pos`` [slots, 1] int64; per cache kind of ``kinds`` (a tuple,
    in the pool's order) ``dec_page_table`` [slots, max_pages] int32 and
    ``dec_write_page`` [slots] int32 (``kind_feed``); ``dec_write_off``
    [slots] int32; with ``state`` ``dec_state_block`` [slots] int32.
    Cached: the builder and the filler are handed the same object."""
    ps = int(pool_slots)
    pieces = [(_DEC_TOK, (ps, 1), "int64"), (_DEC_POS, (ps, 1), "int64")]
    for kind in kinds:
        pieces += [(kind_feed(_DEC_TABLE, kind), (ps, max_pages), "int32"),
                   (kind_feed(_DEC_WRITE_PAGE, kind), (ps,), "int32")]
    pieces.append((_DEC_WRITE_OFF, (ps,), "int32"))
    if state:
        pieces.append((STATE_FEEDS["decode"], (ps,), "int32"))
    return _layout(_DEC_FEED, pieces)


@functools.lru_cache(maxsize=None)
def prefill_layout(kinds, chunk_len, chunk_pages, max_pages, state,
                   index_feed=None):
    """The layout of a prefill chunk's feed ``pf_feed``: ``pf_tok`` /
    ``pf_pos`` [1, C] int64; per cache kind ``pf_page_table``
    [1, max_pages] int32 and ``pf_write_pages`` [``chunk_pages``] int32;
    ``pf_qstart`` [1] int32; ``pf_last_idx`` [1] int64; ``pf_final`` [1]
    int32; with ``state`` ``pf_state_block`` [1] int32; for a lane with
    an image encoder its ``index_feed`` [1, C] int32.  Cached, as
    ``decode_layout``."""
    c = int(chunk_len)
    pieces = [(_PF_TOK, (1, c), "int64"), (_PF_POS, (1, c), "int64")]
    for kind in kinds:
        pieces += [(kind_feed(_PF_TABLE, kind), (1, max_pages), "int32"),
                   (kind_feed(_PF_WRITE_PAGES, kind), (chunk_pages,),
                    "int32")]
    pieces += [(_PF_QSTART, (1,), "int32"), (_PF_LAST_IDX, (1,), "int64"),
               (_PF_FINAL, (1,), "int32")]
    if state:
        pieces.append((STATE_FEEDS["prefill"], (1,), "int32"))
    if index_feed is not None:
        pieces.append((index_feed, (1, c), "int32"))
    return _layout(_PF_FEED, pieces)


def decode_feed(tok, pos, tables, write_page, write_off, state_block=None):
    """One decode step's feed: the one entry ``dec_feed``, every piece
    written into its slice of one int32 buffer as ``decode_layout``
    lays them out.  ``tok`` / ``pos`` [slots, 1]; per cache kind, in the
    pool's order, ``tables`` {kind: [slots, max_pages]} and
    ``write_page`` {kind: [slots]}; ``write_off`` [slots]; for a lane
    with per-sequence state ``state_block`` [slots]."""
    layout = decode_layout(tuple(tables), *next(iter(tables.values())).shape,
                           state_block is not None)
    values = {_DEC_TOK: tok, _DEC_POS: pos, _DEC_WRITE_OFF: write_off,
              STATE_FEEDS["decode"]: state_block}
    for kind, table in tables.items():
        values[kind_feed(_DEC_TABLE, kind)] = table
        values[kind_feed(_DEC_WRITE_PAGE, kind)] = write_page[kind]
    return layout.pack(values)


def prefill_feed(tok, pos, tables, write_pages, q_start, last_idx, final,
                 state_block=None, row_idx=None):
    """One prefill chunk's feed: the one entry ``pf_feed``, packed as
    ``prefill_layout`` lays it out.  ``tok`` / ``pos`` [1, C]; per cache
    kind ``tables`` {kind: [1, max_pages]} and ``write_pages`` {kind:
    [C / page_size]}; ``q_start`` [1] (tokens already in the pool);
    ``last_idx`` [1] (the chunk's last valid row); ``final`` [1] (nonzero
    where the token after that row is read: the chunk then runs the
    head); for a lane with per-sequence state ``state_block`` [1]; for a
    lane with an image encoder ``row_idx`` = (the encoder's index feed,
    [1, C])."""
    kind = next(iter(tables))
    layout = prefill_layout(
        tuple(tables), np.shape(tok)[1], len(write_pages[kind]),
        tables[kind].shape[1], state_block is not None,
        None if row_idx is None else row_idx[0])
    values = {_PF_TOK: tok, _PF_POS: pos, _PF_QSTART: q_start,
              _PF_LAST_IDX: last_idx, _PF_FINAL: final,
              STATE_FEEDS["prefill"]: state_block}
    for kind, table in tables.items():
        values[kind_feed(_PF_TABLE, kind)] = table
        values[kind_feed(_PF_WRITE_PAGES, kind)] = write_pages[kind]
    if row_idx is not None:
        values[row_idx[0]] = row_idx[1]
    return layout.pack(values)


def _declare_feed(layout):
    """The program's side of a packed feed: declares ``layout.feed`` and
    returns {piece: the variable sliced, shaped and cast out of it}."""
    from paddle_tpu import fluid
    from paddle_tpu.fluid import layers as L

    packed = fluid.data(layout.feed, [layout.size], False, dtype="int32")
    out = {}
    for name, (offset, shape, dtype) in layout.pieces.items():
        var = L.slice(packed, axes=[0], starts=[offset],
                      ends=[offset + math.prod(shape)])
        if len(shape) != 1:
            var = L.reshape(var, shape=list(shape))
        out[name] = var if dtype == "int32" else L.cast(var, dtype)
    return out


class Frame(collections.namedtuple("Frame", (
        "tok", "pos", "shape", "tables", "q_start", "pools", "writes",
        "row_valid", "last_idx", "states", "state_block", "image_rows",
        "counted_as", "attn_force"))):
    """What a model's ``decoder(frame)`` is handed: one executable's
    tokens and everything of the engine's its blocks read and write.

    ``tok`` / ``pos`` [B, T] int64 and ``shape`` = (B, T): (slots, 1) in
    a decode step, (1, C) in a chunk.  ``tables`` {kind: page table
    [B, max_pages]} and ``writes`` {kind: ``write(pool, rows)``, which
    writes the tokens' rows [B, T, w] into a pool var of that kind} by
    cache kind (``kind_name``; a lane that declares no windows has the
    one kind ``FULL``); ``q_start`` [B] int32, the tokens ahead of the
    first row; ``pools``: per cache layer its pool vars in the order of
    its declared rows.  ``row_valid`` int32, nonzero where a row is a
    token: [slots] in a decode step (the slot's write page; an inactive
    slot writes the trash page 0), [C] in a chunk (the rows up to
    ``last_idx`` [1] int64, which is None in a decode step).  ``states``
    {layer: its state vars} and ``state_block`` [B] int32 for a lane
    with per-sequence state, else None.  ``image_rows``: None, or (the
    row staging var, the index a position [1, C]) in the chunk of a lane
    with an image encoder.  ``counted_as``: ``"decode"`` / ``"prefill"``,
    the executable device counters book under, None in the
    whole-sequence form, which books nothing.  ``attn_force``: the
    kernels' ``force``."""
    __slots__ = ()


def _page_writer(chunk_len, *where):
    """``write(pool, rows)`` of one cache kind: the tokens' rows into the
    pool at ``where`` = (page [B], offset [B]) in a decode step
    (``chunk_len`` None), (pages [C / page_size],) in a chunk, whose rows
    [1, C, w] are first laid a token a row.  A pool that is a (hi, lo,
    scale) triple is the dual-int8 pool (``kv_rows``): its write
    quantises."""
    from paddle_tpu.fluid import layers as L

    def write(pool, rows):
        if chunk_len is not None and rows.shape[0] != chunk_len:
            rows = L.reshape(rows, shape=[chunk_len, 1, -1])
        if isinstance(pool, tuple):
            op = (L.kv_cache_write_quant if chunk_len is None
                  else L.kv_cache_write_pages_quant)
            op(*pool, rows, *where)
        else:
            op = (L.kv_cache_write if chunk_len is None
                  else L.kv_cache_write_pages)
            op(pool, rows, *where)
    return write


def _kinds(decl):
    """The cache kinds of a lane's layers, ``full`` first, as the pool
    orders them."""
    return [kind_name(w) for w in kinds_of(decl.layer_windows or [None])]


def _by_kind(decl, fed, piece):
    """{kind: the lane's ``piece`` of that cache kind} out of an unpacked
    feed."""
    return {kind: fed[kind_feed(piece, kind)] for kind in _kinds(decl)}


def _declare(decl, num_pages, page_size, pool_dtype, state_blocks):
    """(pool vars a cache layer, {layer: state vars} or None)."""
    pools = declare_pool_vars(
        decl.cache_rows(pool_dtype or decl.pool_dtype), decl.num_layers,
        num_pages, page_size, layer_windows=decl.layer_windows)
    states = (declare_state_vars(decl.seq_state, decl.state_layers,
                                 state_blocks) if decl.seq_state else None)
    return pools, states


def build_decode_step(decl, decoder, head, pool_slots, num_pages, page_size,
                      max_pages, pool_dtype=None, attn_force=None,
                      state_blocks=None):
    """ONE token-level decode step over the paged caches, the single
    fixed-shape executable the scheduler dispatches every step.  Its ONE
    feed is ``dec_feed``, int32 of a length static in ``pool_slots`` /
    ``max_pages`` / the lane's cache kinds and state (no steady-state
    recompile, one host transfer a run), which the program slices,
    shapes and casts apart as ``decode_layout`` lays it out.  Per slot
    s: the token ``dec_tok[s]`` at position ``dec_pos[s]`` goes through
    ``decoder``, which writes each layer's rows at (``dec_write_page[s]``,
    ``dec_write_off[s]``) and attends the slot's prefix through
    ``dec_page_table[s]`` (one table and one write page a cache kind,
    ``kind_feed``; ``num_pages`` is then ``{kind: pages}``), and ``head``
    emits the greedy next token.  Inactive slots carry page-table zeros
    (the trash page), position 0 and, in a lane with state, the trash
    block; their outputs are garbage the scheduler ignores.  Returns
    ``(the feed's layout, next_tok [pool_slots] int64, logprobs
    [pool_slots, vocab])``."""
    from paddle_tpu.fluid import layers as L

    ps = int(pool_slots)
    layout = decode_layout(tuple(_kinds(decl)), ps, int(max_pages),
                           bool(decl.seq_state))
    fed = _declare_feed(layout)
    tok, pos, write_off = fed[_DEC_TOK], fed[_DEC_POS], fed[_DEC_WRITE_OFF]
    tables = _by_kind(decl, fed, _DEC_TABLE)
    write_page = _by_kind(decl, fed, _DEC_WRITE_PAGE)
    block = fed.get(STATE_FEEDS["decode"])
    pools, states = _declare(decl, num_pages, page_size, pool_dtype,
                             state_blocks or ps + 2)
    q_start = L.cast(L.reshape(pos, shape=[-1]), "int32")
    x = decoder(Frame(
        tok=tok, pos=pos, shape=(ps, 1), tables=tables, q_start=q_start,
        pools=pools, writes={kind: _page_writer(None, page, write_off)
                             for kind, page in write_page.items()},
        row_valid=next(iter(write_page.values())), last_idx=None,
        states=states, state_block=block, image_rows=None,
        counted_as="decode", attn_force=attn_force))
    return (layout, *head(x))


def _chunk(decoder, tok, pos, tables, write_pages, q_start, last_idx, pools,
           states, block, image_rows, counted_as, attn_force):
    """One sequence's chunk of tokens through ``decoder``: the hidden
    state of every row, [1, C, D]."""
    from paddle_tpu.fluid import layers as L

    c = int(tok.shape[1])
    row_valid = L.cast(L.less_equal(L.range(0, c, 1, "int64"), last_idx),
                       "int32")
    return decoder(Frame(
        tok=tok, pos=pos, shape=(1, c), tables=tables, q_start=q_start,
        pools=pools, writes={kind: _page_writer(c, pages)
                             for kind, pages in write_pages.items()},
        row_valid=row_valid, last_idx=last_idx, states=states,
        state_block=block, image_rows=image_rows, counted_as=counted_as,
        attn_force=attn_force))


def build_prefill_chunk(decl, decoder, head, chunk_len, num_pages, page_size,
                        max_pages, pool_dtype=None, attn_force=None,
                        state_blocks=None, image_rows=None):
    """One prefill CHUNK of a single sequence through the paged caches:
    long prompts stream through this fixed-shape executable
    ``ceil(P / chunk_len)`` times, each call writing the chunk's rows
    into whole pool pages (``chunk_len`` is a multiple of ``page_size``)
    and attending what was written before through the page table.

    Its ONE feed is ``pf_feed``, int32 of a static length, sliced apart
    inside the program as ``prefill_layout`` lays it out
    (``prefill_feed`` packs it): ``pf_tok`` / ``pf_pos`` [1, C] int64
    (positions clamped host-side for the padded tail); a cache kind
    ``pf_page_table`` [1, max_pages] int32 and ``pf_write_pages``
    [C / page_size] int32 (the trash page 0 past the valid tail);
    ``pf_qstart`` [1] int32, the tokens already in the pool;
    ``pf_last_idx`` [1] int64, the last VALID row of the chunk;
    ``pf_final`` [1] int32, nonzero where the token after that row is
    consumed (a prompt's final chunk): ``head`` runs under it, in the
    true branch of ONE conditional of the one executable, so every other
    chunk reads no head weight and returns token 0 and zeros for the
    log-probabilities; in a lane with state
    ``pf_state_block`` [1] int32, read as zeros where ``pf_qstart`` is 0
    and carried to the next chunk (rows past ``pf_last_idx`` leave it
    alone); in a lane with an image encoder its index piece [1, C] int32
    (``image_rows``: the rows of the engine's staging var), -1 where the
    position is a token: a chunk of tokens carries -1 throughout, one
    executable.  Returns ``(the feed's layout, next_tok [1] int64,
    logprobs [1, vocab])``."""
    from paddle_tpu.fluid import layers as L

    c = int(chunk_len)
    if c % int(page_size):
        raise ValueError(
            f"prefill chunk_len {c} must be a multiple of page_size "
            f"{page_size} (chunks write whole pages)")
    enc = decl.encoder if image_rows is not None else None
    layout = prefill_layout(
        tuple(_kinds(decl)), c, c // int(page_size), int(max_pages),
        bool(decl.seq_state), enc.index_feed if enc is not None else None)
    fed = _declare_feed(layout)
    tok, pos = fed[_PF_TOK], fed[_PF_POS]
    q_start, last_idx = fed[_PF_QSTART], fed[_PF_LAST_IDX]
    tables = _by_kind(decl, fed, _PF_TABLE)
    write_pages = _by_kind(decl, fed, _PF_WRITE_PAGES)
    block = fed.get(STATE_FEEDS["prefill"])
    pools, states = _declare(decl, num_pages, page_size, pool_dtype,
                             state_blocks or 2)
    staged = None
    if enc is not None:
        staged = (declare_row_staging(image_rows, enc.row_width),
                  fed[enc.index_feed])
    x = _chunk(decoder, tok, pos, tables, write_pages, q_start, last_idx,
               pools, states, block, staged, "prefill", attn_force)
    width = int(x.shape[-1])
    read = L.ConditionalBlock([fed[_PF_FINAL]])
    with read.block():
        # an exact copy of the last valid row: the final chunk's output
        # seeds the decode loop's first token
        h_last = L.reshape(
            L.gather(L.reshape(x, shape=[-1, width]), last_idx),
            shape=[-1, 1, width])
        next_tok, logp = (read.output(out) for out in head(h_last))
    return layout, next_tok, logp


def build_whole_sequence(decl, decoder, head, seq_len, page_size=None,
                         attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] after every
    position of ``pf_tok`` [1, S] (fed with ``pf_pos``).  The chunk's
    blocks over caches and state that live and die inside the program:
    every cache kind under the identity page table (nothing is given
    back), state block 1 of 2, read as zeros.  Books no device counter
    and takes no image.  No served program: its two feeds stay two, by
    name (the references feed it so)."""
    from paddle_tpu import fluid
    from paddle_tpu.fluid import layers as L

    c = int(seq_len)
    page = int(page_size or min(c, 128))
    if c % page:
        raise ValueError(f"seq_len {c} must be a multiple of page {page}")
    n = c // page
    tok = fluid.data(_PF_TOK, [1, c], False, dtype="int64")
    pos = fluid.data(_PF_POS, [1, c], False, dtype="int64")
    page_table = L.reshape(L.cast(L.range(1, n + 1, 1, "int64"), "int32"),
                           shape=[1, n])
    q_start = L.fill_constant(shape=[1], value=0, dtype="int32")
    last_idx = L.fill_constant(shape=[1], value=c - 1, dtype="int64")
    block = states = None
    if decl.seq_state:
        block = L.fill_constant(shape=[1], value=1, dtype="int32")
    pools = [tuple(L.fill_constant(shape=[n + 1, page, row.width], value=0.0,
                                   dtype=row.dtype) for row in rows)
             for rows in rows_of_layers(decl.cache_rows(decl.pool_dtype),
                                        decl.num_layers, decl.layer_windows)]
    if decl.seq_state:
        states = {layer: tuple(L.fill_constant(
            shape=[2, *st.shape], value=0.0, dtype=st.dtype)
            for st in decl.seq_state) for layer in decl.state_layers}
    kinds = _kinds(decl)
    x = _chunk(decoder, tok, pos, dict.fromkeys(kinds, page_table),
               dict.fromkeys(kinds, L.reshape(page_table, shape=[n])),
               q_start, last_idx, pools, states, block, None, None,
               attn_force)
    return head(L.reshape(x, shape=[c, 1, int(x.shape[-1])]))[1]


def scaffold(decoder, head, **declaration):
    """A model's ``DecodeLane`` (``declaration``: its keywords but the
    two builders) whose builders are this module's frames around the
    model's ``decoder(frame)`` -> hidden [B, T, D] and ``head(h [N, 1,
    D])`` -> (next token [N] int64, logprobs [N, V]); its
    ``build_whole_sequence(seq_len, page_size=, attn_force=)`` builds
    the whole-sequence form around the same two."""
    decl = DecodeLane(build_decode_step=None, build_prefill_chunk=None,
                      **declaration)
    decl.build_decode_step = functools.partial(build_decode_step, decl,
                                               decoder, head)
    decl.build_prefill_chunk = functools.partial(build_prefill_chunk, decl,
                                                 decoder, head)
    decl.build_whole_sequence = functools.partial(build_whole_sequence, decl,
                                                  decoder, head)
    return decl
