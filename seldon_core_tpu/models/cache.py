"""The cache tree: what a layer's entry is made of, and how rows get in and
out of it.

One entry a layer. An ATTENTION layer's is ``(arrays..., positions)``: bf16
``(k, v, pos)`` with a token's heads split ([.., kvh, hd]) or as one flat row
([.., kvh * hd], ``cfg.kv_rows_flat``), int8 ``(kq, ks, vq, vs, pos)``, latent
``(rows, pos)``; leading dims [b, max_len] (dense: ``init_kv_caches``,
``generate()``, the draft model) or [pages, page_size] (a pool shared through
block tables, the vLLM / PagedAttention design: ``init_paged_kv_caches``, the
continuous batcher). ``positions`` is PAD_POS where a row is empty, so ONE
predicate (``pos <= query position``) is causality, the unwritten rest and
padding. A STATE layer's entry is a ``StateEntry``: fixed blocks a sequence.
A paged pool's pages are of one of two CLASSES, each with its own pool length,
block tables and allocator (runtime/batcher.py): ``full`` (a page for every 64
tokens of the sequence, kept while the request lives) and ``window`` (a
sliding-attention layer's, a ``WindowEntry``: the pages behind the window are
given back while the request lives, so a slot needs a window, a chunk and a
page of them whatever its length).

The reads (models/transformer.py ``paged_live_read``, ops/page_walk.py) choose
a kernel, not a form, and stay there. Nothing is imported here from
models/transformer.py, runtime/ or servers/ (``cfg`` is an argument): they
import this (tests/test_cache_tree.py).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

# the matrix state's layout is the step kernels' (they read the array as it lies)
from seldon_core_tpu.ops.gated_delta import heads_a_lane_row, pack_state, unpack_state

# Sentinel position for empty/padded cache slots and padded prompt tokens:
# larger than any real position, so causal masks (key_pos <= query_pos)
# exclude them; small enough that rotary angles stay finite.
PAD_POS = 1 << 28

# KV-cache storage formats. "bf16" stores K/V in the model compute dtype
# (named for the production config); "int8" stores symmetric per-head,
# per-position int8 values plus f32 scales: half the bytes a read streams.
KV_CACHE_DTYPES = ("bf16", "int8")
_KV_QMAX = 127.0

# Reserved page ids in every paged pool. NULL_PAGE backs unallocated
# block-table tail entries: its position row is PAD_POS forever (writes
# through a NULL entry are redirected device-side), so gathering it always
# reads as "masked, never attended". TRASH_PAGE absorbs garbage writes —
# inactive batcher slots ride along in the static-shape decode step, and
# their stale writes must land somewhere no live block table points.
NULL_PAGE = 0
TRASH_PAGE = 1
RESERVED_PAGES = 2


def normalize_kv_cache_dtype(value) -> str:
    """Canonical kv_cache_dtype ("bf16" or "int8"); raises ValueError on
    anything else so misconfiguration fails at load() time, not inside jit."""
    v = str(value or "bf16").strip().lower()
    if v in ("bf16", "bfloat16", "model", "default"):
        return "bf16"
    if v == "int8":
        return "int8"
    raise ValueError(
        f"unknown kv_cache_dtype {value!r}: expected one of {KV_CACHE_DTYPES}"
    )


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization over the last (head_dim) axis:
    x [..., hd] float -> (q int8 [..., hd], scale f32 [...]). One scale per
    head per position — finer than per-tensor, so attention logits survive
    outlier keys; zero vectors get scale 1 (dequantize to exact zeros)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / _KV_QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -128, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of quantize_kv, used INSIDE the attention read so XLA fuses
    the convert+multiply into the consuming einsum (int8 stays the HBM
    format; dequant happens on the fly in VMEM)."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


LATENT_INT8_REFUSAL = (
    "kv_cache_dtype='int8' is not built for latent attention (kv_lora_rank "
    "> 0): a latent row has no head axis to scale by, and a per-row scale "
    "over 512 + 64 mixed values is untested; serve it with the bf16 cache")


class StateEntry(tuple):
    """A state layer's entry of a cache tree (a fixed block a sequence, no
    pages, no positions: a conv layer's ``(state,)``, a linear-attention
    layer's ``(conv_state, S)``, the EMPTY entry of a layer that keeps nothing
    of its own: a gated memory unit, a cross-attention layer, which reads
    another layer's pages in place), told from an attention layer's
    ``(values..., positions)`` by its TYPE: the initialisers below make one
    where ``cfg.layer_kind`` names a state layer, ``put_state`` returns one,
    and every tree operation keeps it (a registered pytree node)."""


jax.tree_util.register_pytree_node(
    StateEntry, lambda entry: (tuple(entry), None), lambda _aux, leaves: StateEntry(leaves))


class WindowEntry(tuple):
    """A sliding-attention layer's entry of a PAGED tree: the arrays of an
    attention entry, ``(values..., positions)``, whose pages are of the WINDOW
    class (a pool of its own length, addressed through the window block tables).
    Told from a full layer's plain tuple by its TYPE, as a state entry is; every
    write and tree operation keeps it."""


jax.tree_util.register_pytree_node(
    WindowEntry, lambda entry: (tuple(entry), None), lambda _aux, leaves: WindowEntry(leaves))

def is_window_entry(layer) -> bool:
    return isinstance(layer, WindowEntry)


def _like(entry, values):
    """``values`` as an entry of ``entry``'s page class."""
    return WindowEntry(values) if isinstance(entry, WindowEntry) else tuple(values)


def window_slot_pages(window: int, widest_call: int, page_size: int) -> int:
    """Pages of the window class ONE sequence can hold at once: its window, the
    widest call's rows (a prefill chunk's) and one page of rounding, in whole
    pages. Before a call whose first query is at p0 the pages wholly behind
    p0 - window + 1 are given back, and the call writes up to p0 + widest - 1."""
    return -(-(window + widest_call) // page_size) + 1


def is_state_entry(layer) -> bool:
    """Is this layer's entry of a cache tree a state layer's? The page
    operations skip it."""
    return isinstance(layer, StateEntry)


def state_lane_heads(cfg) -> int:
    """How many value heads' [dk, dv] share a lane row of the matrix state: the
    step kernel's rule (ops/gated_delta.py reads the array as it lies)."""
    return heads_a_lane_row(cfg.linear_num_value_heads, cfg.linear_value_head_dim)


def _state_entry_shapes(cfg, kind: str) -> Tuple[Tuple[Tuple[int, ...], Any], ...]:
    """(shape a sequence, dtype) of each array of a state layer's entry."""
    if kind == "conv":
        return (((cfg.conv_L_cache - 1, cfg.dim), cfg.dtype),)
    if kind in ("gmu", "cross_attention"):
        # nothing of its own: a "gmu" layer reads the call's rows of another
        # layer's scan output, a "cross_attention" layer another layer's pages
        return ()
    if kind == "s6":
        # (the rows of x before the taps; h float32 [d_state, d_inner]: the
        # states along the sublanes, the channels along the lanes, the layout
        # of ops/selective_scan.py's kernel)
        return (((cfg.mamba_d_conv - 1, cfg.mamba_d_inner), cfg.dtype),
                ((cfg.mamba_d_state, cfg.mamba_d_inner), jnp.float32))
    if kind == "mamba":
        # (the rows of [x ; B ; C] before the taps; h float32, a head's TRANSPOSED
        # [d_state, d_head], ``side`` heads side by side along the lanes as the
        # delta rule's S is (``pack_state``): the step kernel's layout,
        # ops/ssd.py; granite's 64 heads of [64, 128] are 32 units of [128, 128])
        channels = (cfg.mamba_n_heads * cfg.mamba_d_head
                    + 2 * cfg.mamba_n_groups * cfg.mamba_d_state)
        side = heads_a_lane_row(cfg.mamba_n_heads, cfg.mamba_d_head)
        return (((cfg.mamba_d_conv - 1, channels), cfg.dtype),
                ((cfg.mamba_n_heads // side, cfg.mamba_d_state, side * cfg.mamba_d_head),
                 jnp.float32))
    channels = (2 * cfg.linear_num_key_heads * cfg.linear_key_head_dim
                + cfg.linear_num_value_heads * cfg.linear_value_head_dim)
    side = state_lane_heads(cfg)
    return (((cfg.linear_conv_kernel_dim - 1, channels), cfg.dtype),
            ((cfg.linear_num_value_heads // side, cfg.linear_key_head_dim,
              side * cfg.linear_value_head_dim), jnp.float32))


def state_bytes(cfg) -> int:
    """Bytes of state ONE sequence keeps over all its state layers, whatever
    its length (0 for a model without them)."""
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize
               for i in cfg.state_layers
               for shape, dtype in _state_entry_shapes(cfg, cfg.layer_kind(i)))


def _with_state_entries(cfg, attention_entries: list, rows: int):
    """The cache tree over ALL layers: a state layer's entry (zeros,
    ``rows`` sequences) where cfg.layer_types says so, the attention entries
    in order elsewhere."""
    if not cfg.state_layers:
        return attention_entries
    if rows <= 0:
        raise ValueError(
            "a model with state layers needs the number of sequences its state "
            "blocks serve (init_paged_kv_caches(..., state_slots=))")
    entries = iter(attention_entries)
    return [
        StateEntry(jnp.zeros((rows,) + shape, dtype)
                   for shape, dtype in _state_entry_shapes(cfg, cfg.layer_kind(i)))
        if i in cfg.state_layers else next(entries)
        for i in range(cfg.n_layers)
    ]


def _init_latent_caches(cfg, lead: Tuple[int, int], kvd: str):
    """(rows, pos) per layer with leading dims ``lead``: [b, max_len] dense
    or [pages, page_size] paged."""
    if kvd == "int8":
        raise ValueError(LATENT_INT8_REFUSAL)
    return [
        (jnp.zeros(lead + (cfg.latent_row_dim,), dtype=cfg.dtype),
         jnp.full(lead, PAD_POS, dtype=jnp.int32))
        for _ in range(cfg.n_layers)
    ]


def _init_head_caches(cfg, lead: Tuple[int, int], kvd: str,
                      flat: bool = False, window_lead: Optional[Tuple[int, int]] = None):
    """Per-head K/V entries with leading dims ``lead``, one per ATTENTION
    layer: (k, v, pos), or the int8 5-tuple. ``flat``: a token's heads as one
    row (the paged bf16 pool of a cfg.kv_rows_flat model). ``window_lead``: a
    paged tree's sliding-attention layers are ``WindowEntry``s of that many
    pages (the window class's pool)."""
    def entry(lead):
        shape = lead + (cfg.n_kv_heads, cfg.head_dim)
        if flat and kvd != "int8":
            shape = lead + (cfg.n_kv_heads * cfg.head_dim,)

        def values():   # of K, and again of V
            if kvd == "int8":
                return (jnp.zeros(shape, dtype=jnp.int8),
                        jnp.ones(lead + (cfg.n_kv_heads,), dtype=jnp.float32))
            return (jnp.zeros(shape, dtype=cfg.dtype),)

        return values() + values() + (jnp.full(lead, PAD_POS, dtype=jnp.int32),)

    windowed = set(getattr(cfg, "window_layers", ())) if window_lead is not None else ()
    return [WindowEntry(entry(window_lead)) if i in windowed else entry(lead)
            for i in range(cfg.n_layers) if i not in cfg.state_layers]


def init_kv_caches(cfg, batch: int, max_len: int,
                   kv_cache_dtype: Optional[str] = None):
    """The DENSE tree (``generate()``, the draft model): an entry a layer with
    leading dims [batch, max_len], every position PAD_POS (never attended),
    int8 scales 1 (an empty row dequantizes to exact zeros); a state layer's
    blocks [batch, ...] (``_state_entry_shapes``)."""
    kvd = normalize_kv_cache_dtype(kv_cache_dtype or cfg.kv_cache_dtype)
    if cfg.kv_lora_rank:
        return _init_latent_caches(cfg, (batch, max_len), kvd)
    return _with_state_entries(
        cfg, _init_head_caches(cfg, (batch, max_len), kvd), batch)


def init_paged_kv_caches(cfg, num_pages: int,
                         page_size: int, kv_cache_dtype: Optional[str] = None,
                         state_slots: int = 0, window_pages: int = 0):
    """The PAGED tree (the continuous batcher): leading dims [num_pages,
    page_size], pages shared by every sequence through block tables. Pages 0
    and 1 are reserved (NULL_PAGE / TRASH_PAGE), so the pool serves
    ``num_pages - RESERVED_PAGES`` pages of tokens. A state layer has no
    pages: fixed blocks [state_slots, ...], one a sequence the pool serves.
    A sliding-attention layer's pool is ``window_pages`` long (the window
    class, with its own two reserved pages), a ``WindowEntry``.

    Where ``cfg.kv_rows_flat`` (one device, or narrow heads) the bf16
    pool holds a token's heads as ONE row, [num_pages, page_size, kvh * hd];
    elsewhere, and the int8 pool beside its [.., kvh] scales, [.., kvh, hd]
    (the split of a whole view of flat rows is two to three times the step's
    read as an expression: v5e, PR 36)."""
    if num_pages <= RESERVED_PAGES:
        raise ValueError(
            f"paged KV pool needs > {RESERVED_PAGES} pages "
            f"(got {num_pages}; pages 0/1 are reserved)")
    kvd = normalize_kv_cache_dtype(kv_cache_dtype or cfg.kv_cache_dtype)
    if cfg.kv_lora_rank:
        return _init_latent_caches(cfg, (num_pages, page_size), kvd)
    window_lead = None
    if getattr(cfg, "window_layers", ()):
        if window_pages <= RESERVED_PAGES:
            raise ValueError(
                f"a model with sliding-attention layers needs a window-class pool of > "
                f"{RESERVED_PAGES} pages (init_paged_kv_caches(..., window_pages=); got "
                f"{window_pages})")
        window_lead = (window_pages, page_size)
    pools = _init_head_caches(cfg, (num_pages, page_size), kvd, flat=cfg.kv_rows_flat,
                              window_lead=window_lead)
    return _with_state_entries(cfg, pools, state_slots)


def kv_cache_bytes_per_token(cfg, kv_cache_dtype: Optional[str] = None,
                             page_class: Optional[str] = None) -> int:
    """HBM bytes one cached token position costs across all layers, or across
    the layers of one ``page_class`` ("full" | "window"; a window layer holds a
    token only while it lies inside the window) (K + V
    values, int8 scales when quantized, and the int32 position map): what a
    page pool of N tokens is billed, and times a sequence's LIVE rows (in
    whole visits) what a decode step's read of it streams where the kernel
    walks the live pages (every bf16 pool on one TPU since PR 36; the whole
    block-table view elsewhere)."""
    kvd = normalize_kv_cache_dtype(kv_cache_dtype or cfg.kv_cache_dtype)
    if cfg.kv_lora_rank:   # one latent row for all heads, in the model dtype
        return cfg.n_layers * (cfg.latent_row_dim * jnp.dtype(cfg.dtype).itemsize + 4)
    per_pos = cfg.n_kv_heads * cfg.head_dim
    if kvd == "int8":
        per_layer = 2 * (per_pos * 1 + cfg.n_kv_heads * 4)  # int8 + f32 scale
    else:
        per_layer = 2 * per_pos * jnp.dtype(cfg.dtype).itemsize
    # a state layer caches nothing a token (state_bytes a sequence)
    layers = cfg.n_layers - len(cfg.state_layers)
    if page_class is not None:
        windowed = len(getattr(cfg, "window_layers", ()))
        layers = windowed if page_class == "window" else layers - windowed
    return layers * (per_layer + 4)  # + int32 pos slot


def paged_write_targets(block_tables: jnp.ndarray, positions: jnp.ndarray,
                        page_size: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(page, offset) pool coordinates for writing each token's KV.

    ``block_tables``: [b, n_pages] page ids; ``positions``: [b, s] absolute
    token positions (PAD_POS for padding). Tokens whose position falls past
    the table, or whose table entry is NULL_PAGE (unallocated — the host
    failed to provision, or an inactive batcher slot riding along in the
    static-shape step), are redirected to TRASH_PAGE: the null page's
    PAD_POS position row is a device-side invariant no write may break."""
    p = positions.astype(jnp.int32)
    n_pages = block_tables.shape[1]
    page_idx = p // page_size
    valid = (p >= 0) & (page_idx < n_pages)
    entry = jnp.take_along_axis(
        block_tables, jnp.clip(page_idx, 0, n_pages - 1), axis=1)
    entry = jnp.where(valid & (entry != NULL_PAGE), entry, TRASH_PAGE)
    return entry, p % page_size


def paged_write_by_page(cache, b: int, s: int) -> bool:
    """Whether a call's rows land in the paged pool as whole pages
    (``paged_write_pages``) or one scatter row a token, from what the call
    shows alone: a pool of flat rows [pages, page_size, width] beside its
    positions (the bf16 K / V 3-tuple, the latent 2-tuple) and ONE sequence's
    run of at least a page — the batcher's prefill chunk.
    The decode step (a token a slot), the speculative verify (a few tokens a
    slot), the int8 5-tuple pool and a pool with its head axes split out
    (``[.., kvh, hd]``: a mesh) keep the token scatter. ``Attention``,
    ``LatentAttention`` and the loop's ``seldon_llm_kv_pages_written_total``
    read this one rule."""
    return len(cache) in (2, 3) and cache[0].ndim == 3 and b == 1 and s >= cache[0].shape[1]


def pages_a_run_writes(s: int, page_size: int) -> int:
    """Whole pages ``paged_write_pages`` reads and writes back for a run of
    ``s`` rows: those a run that starts anywhere in a page can reach."""
    return -(-s // page_size) + 1


@jax.jit
def paged_write_pages(pools, pos_pool: jnp.ndarray, block_tables: jnp.ndarray,
                      positions: jnp.ndarray, rows):
    """Write ONE sequence's run of rows into pools of flat rows
    [pages, page_size, width] (``pools``, one of ``rows`` [s, width] each) and
    its positions into ``pos_pool`` [pages, page_size], a page at a time.

    ``positions`` [1, s] is what a prefill chunk carries: column ``j`` holds
    ``positions[0, 0] + j`` or PAD_POS (padding), so the run lies in the
    ``s // page_size + 1`` (rounded up) consecutive pages of the sequence from
    the one that holds its start, wherever in that page it starts (a
    copy-on-write prefix hit starts mid-page). Those pages are read, the live
    rows laid into them at their offsets, and written back whole: a row that is
    padding or lies outside the run keeps its old value and position, so every
    page the sequence holds is bit for bit what the token scatter
    (``paged_write_targets``) leaves. The pages' pool entries come from that
    same function: a page past the table or one whose entry is NULL_PAGE lands
    on TRASH_PAGE, the only page that may differ. Returns (pools, pos_pool).
    A jitted function of its own, so a program's layers share ONE trace of it
    (a trace a layer was +0.5 s of every chunk program's start on the chip's
    host, PR 42); XLA inlines the call, and the donated pools are still
    updated in place."""
    ps = pos_pool.shape[1]
    n = pages_a_run_writes(positions.shape[1], ps)
    p = positions[0].astype(jnp.int32)
    first, off = p[0] // ps, p[0] % ps
    entry = paged_write_targets(block_tables[:1], ((first + jnp.arange(n)) * ps)[None], ps)[0][0]
    # the run's positions at their rows of the n pages; PAD_POS = keep the old row
    laid_pos = jax.lax.dynamic_update_slice(jnp.full((n * ps,), PAD_POS, jnp.int32), p, (off,))
    live = laid_pos < PAD_POS

    def read(pool):   # the n pages as one run of rows
        return pool[entry].reshape((n * ps,) + pool.shape[2:])

    def write(pool, run):
        return pool.at[entry].set(run.reshape((n,) + pool.shape[1:]))

    written = []
    for pool, new in zip(pools, rows):
        old = read(pool)
        laid = jax.lax.dynamic_update_slice(old, new, (off, 0))
        written.append(write(pool, jnp.where(live[:, None], laid, old)))
    old_pos = read(pos_pool)
    return tuple(written), write(pos_pool, jnp.where(live, laid_pos.astype(old_pos.dtype), old_pos))


def gather_paged_view(cache, block_tables: jnp.ndarray, dtype, n_kv_heads: int):
    """Gather a paged pool back into the per-sequence logical view:
    (k_all, v_all, pos_view) of [b, n_pages*page_size, kvh, hd] / [b, L].

    The ONE copy of the block-table read semantics of the expression: the
    attention read below and ``paged_attention_ref`` (the live-page kernel's
    oracle) both address the pool through this gather. A bf16 pool
    of flat rows [pages, page_size, kvh * hd] (``cfg.kv_rows_flat``) has its
    heads split here; int8 pools (5-tuple,
    [.., kvh, hd] values beside [.., kvh] scales) dequantize here. The gather
    moves bytes, never arithmetic, so the view feeds
    ``grouped_query_attention`` exactly as the dense layout would, n_kv_heads
    wide."""
    bt = jnp.asarray(block_tables, jnp.int32)
    b = bt.shape[0]
    ps = cache[0].shape[1]
    L = bt.shape[1] * ps
    if len(cache) == 5:
        kq_pool, ks_pool, vq_pool, vs_pool, pos_pool = cache
        kvh, hd = kq_pool.shape[2], kq_pool.shape[3]
        k_all = dequantize_kv(kq_pool[bt].reshape(b, L, kvh, hd),
                              ks_pool[bt].reshape(b, L, kvh), dtype)
        v_all = dequantize_kv(vq_pool[bt].reshape(b, L, kvh, hd),
                              vs_pool[bt].reshape(b, L, kvh), dtype)
    else:
        k_pool, v_pool, pos_pool = cache
        k_all = k_pool[bt].reshape(b, L, n_kv_heads, -1)
        v_all = v_pool[bt].reshape(b, L, n_kv_heads, -1)
    return k_all, v_all, pos_pool[bt].reshape(b, L)


def entry_is_int8(entry) -> bool:
    """An int8 entry's writer hands ``write_rows`` ``(*quantize_kv(k), *quantize_kv(v))``."""
    return entry[0].dtype == jnp.int8


def write_rows(entry, rows, positions, *, block_tables=None, cache_index=None):
    """THE write of an attention layer's call into its entry; returns the new
    entry. ``rows``: the call's new rows [b, s, ...] for each of the entry's
    arrays, in its order (``(k, v)``, ``(kq, ks, vq, vs)``, ``(row,)``), cast
    and reshaped here to the rows the entry holds; ``positions`` [b, s],
    PAD_POS for padding. Each addressing exists ONCE, over the arrays in the
    tuple's order, positions last:

    - ``block_tables`` [b, n_pages]: a pool. ONE sequence's run of at least a
      page into flat rows (``paged_write_by_page``) goes in as whole pages;
      every other call scatters a row a token at ``paged_write_targets``
      (padding and unallocated pages land on TRASH_PAGE).
    - dense, ``cache_index`` a scalar: one offset for the batch (prefill); a
      [b] vector: each sequence's offset for its one token or, with s > 1 (the
      speculative verify), every token at its own position, PAD_POS dropped."""
    *arrays, pos = entry
    b, s = positions.shape
    new = [r.astype(a.dtype).reshape((b, s) + a.shape[2:])
           for a, r in zip(entry, (*rows, positions))]
    if block_tables is not None:
        bt = jnp.asarray(block_tables, jnp.int32)
        if paged_write_by_page(entry, b, s):
            written, pos = paged_write_pages(
                tuple(arrays), pos, bt, positions, tuple(r[0] for r in new[:-1]))
            return _like(entry, written + (pos,))
        at = paged_write_targets(bt, positions, pos.shape[1])
    else:
        idx = jnp.asarray(cache_index, dtype=jnp.int32)
        if idx.ndim == 0:
            return tuple(
                jax.lax.dynamic_update_slice(a, r, (0, idx) + (0,) * (a.ndim - 2))
                for a, r in zip(entry, new))
        if s == 1:
            at, new = (jnp.arange(b), idx), [r[:, 0] for r in new]
        else:
            at = (jnp.arange(b)[:, None], positions.astype(jnp.int32))
    return _like(entry, (a.at[at].set(r, mode="drop") for a, r in zip(entry, new)))


def dense_view(entry, dtype):
    """(k_all, v_all, positions) of a DENSE K/V entry as the read takes them
    (int8 dequantizes here: XLA fuses it into the attention einsums)."""
    if entry_is_int8(entry):
        kq, ks, vq, vs, pos = entry
        return dequantize_kv(kq, ks, dtype), dequantize_kv(vq, vs, dtype), pos
    return entry


def state_rows(entry, state_slots, n: int):
    """The ``n`` state arrays a call's sequences continue: row i sequence i's
    (``state_slots`` None), or the rows ``state_slots`` [b] int32 names (a
    chunk's one sequence: its slot). No cache: ``n`` Nones (from zeros)."""
    if entry is None:
        return (None,) * n
    return tuple(entry) if state_slots is None else tuple(a[state_slots] for a in entry)


def put_state(entry, state_slots, new_arrays) -> StateEntry:
    """The entry ``state_rows`` read with the call's new state in it, in the
    entry's dtypes: the new arrays themselves, or the pool with the named rows
    set and every other slot's as it was."""
    if entry is None:
        return StateEntry(new_arrays)
    if state_slots is None:
        return StateEntry(n.astype(a.dtype) for a, n in zip(entry, new_arrays))
    return StateEntry(a.at[state_slots].set(n.astype(a.dtype))
                      for a, n in zip(entry, new_arrays))


def matrix_state_layer(cfg) -> Optional[int]:
    """The first mamba or s6 layer (the layer whose h a probe may read back), or None."""
    return next((i for i in range(cfg.n_layers) if cfg.layer_kind(i) in ("mamba", "s6")), None)


def read_matrix_state(cfg, tree, state_slots):
    """The h that layer holds for the sequences ``state_slots`` [b] names, a head
    at a time and TRANSPOSED as the cache holds it, [b, H, d_state, d_head]
    float32, whatever the lanes' layout: what a probe that asked for "state" is
    sent (runtime/batcher.py ``_read_state``). An s6 layer has no heads: its
    [d_state, d_inner] goes out as blocks of 128 channels (all of them where
    they are no whole number of such blocks) standing where heads do."""
    layer = matrix_state_layer(cfg)
    h = tree[layer][1][state_slots]
    if cfg.layer_kind(layer) == "s6":
        b, states, channels = h.shape
        lanes = 128 if channels % 128 == 0 else channels
        return jnp.swapaxes(h.reshape(b, states, channels // lanes, lanes), 1, 2)
    return unpack_state(h, heads_a_lane_row(cfg.mamba_n_heads, cfg.mamba_d_head))


# --- over a whole tree: plain functions the loop jits, donates and caches ----
# (runtime/batcher.py ``_page_table_ops``, servers/llmserver.py); each hands a
# state entry on as it is.

def first_paged(tree):
    """The first PAGED (attention) layer's entry of a tree."""
    return next(layer for layer in tree if not is_state_entry(layer))


def state_nbytes(tree) -> int:
    """Bytes of ALL the arrays of a tree's state entries."""
    return sum(int(leaf.nbytes) for layer in tree if is_state_entry(layer) for leaf in layer)


def tiled_nbytes(shape: Tuple[int, ...], dtype) -> int:
    """What the chip holds for an array of ``shape``: its last two axes rounded
    up to whole tiles, (8, 128) of 32-bit values ((16, 128) of 16-bit ones)."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sublanes = 8 * 4 // item
    return (math.prod(lead) * (-(-rows // sublanes) * sublanes)
            * (-(-lanes // 128) * 128) * item)


def matrix_state_nbytes(tree) -> Tuple[int, int]:
    """(the arrays' own bytes, the bytes the chip holds for them as it tiles
    them) of the float32 MATRIX state alone: the second array of every
    linear-attention or mamba entry of a tree (a conv entry has one array, rows). Equal
    where no lane or sublane of S is padding."""
    mats = [layer[1] for layer in tree if is_state_entry(layer) and len(layer) == 2]
    return (sum(math.prod(m.shape) * jnp.dtype(m.dtype).itemsize for m in mats),
            sum(tiled_nbytes(m.shape, m.dtype) for m in mats))


def _attention_entries(tree, fn):
    return [layer if is_state_entry(layer) else fn(layer) for layer in tree]


def reset_pages(tree, page_ids, window_page_ids=None):
    """The position rows of pages ``page_ids`` back to PAD_POS: a page off the
    free list still holds its last owner's positions. ``page_ids`` is padded
    with TRASH_PAGE to a fixed length, so one compile serves every size.
    ``window_page_ids``: the same for the window class's pools (a
    ``WindowEntry``'s page ids are its own class's; None leaves them alone)."""
    def reset(layer):
        ids = window_page_ids if is_window_entry(layer) else page_ids
        if ids is None:
            return layer
        return _like(layer, layer[:-1] + (layer[-1].at[ids].set(PAD_POS),))
    return _attention_entries(tree, reset)


WINDOW_RESTART_REFUSAL = (
    "is not built over sliding-attention layers: what restarts a sequence mid-way needs "
    "the window's pages at the restart boundary, and pages behind a window were given back")


def _no_window_entries(tree, what: str) -> None:
    if any(is_window_entry(layer) for layer in tree):
        raise ValueError(f"{what} {WINDOW_RESTART_REFUSAL}")


def forget_positions(tree, positions, block_tables=None):
    """The rows at ``positions`` [b, k] made unattendable (position back to
    PAD_POS: the speculative step's repair of rejected drafts). A PAD_POS
    entry names no row (dense: dropped; a pool: TRASH_PAGE)."""
    _no_window_entries(tree, "forget_positions (the speculative step's rollback)")
    if block_tables is None:
        at = (jnp.arange(positions.shape[0])[:, None], positions)
    else:
        at = paged_write_targets(block_tables, positions, first_paged(tree)[-1].shape[1])
    return _attention_entries(
        tree, lambda layer: layer[:-1] + (layer[-1].at[at].set(PAD_POS, mode="drop"),))


def cow_page_copy(tree, src, dst, n_valid):
    """Page ``src`` copied to page ``dst`` (the radix cache's copy-on-write):
    values whole, the position row only up to the source's VALID length (it
    may carry a previous occupant's run-ahead positions past its credited
    history; copied live, the new slot would attend another sequence's tail)."""
    _no_window_entries(tree, "cow_page_copy (the radix cache's copy-on-write)")

    def copy(layer):
        *vals, pos = layer
        row = jnp.where(jnp.arange(pos.shape[1]) < n_valid, pos[src], PAD_POS)
        return tuple(v.at[dst].set(v[src]) for v in vals) + (pos.at[dst].set(row),)
    return _attention_entries(tree, copy)


def export_pages(tree, idx):
    """Pages ``idx`` of every attention entry as a staged handoff-shaped
    bucket (no state entry travels: state is a slot's)."""
    _no_window_entries(tree, "export_pages (the disaggregated hand-off)")
    return [tuple(pool[idx] for pool in layer) for layer in tree
            if not is_state_entry(layer)]


def import_pages(tree, staged, block_row, n_valid, m: int):
    """The first ``m`` sequence pages of a staged bucket (``export_pages``'
    form, behind its RESERVED_PAGES rows) scattered into the pool pages
    ``block_row`` names; rows past ``n_valid`` and NULL entries go to
    TRASH_PAGE, so one compile serves every prompt length inside a bucket."""
    _no_window_entries(tree, "import_pages (the disaggregated hand-off)")
    src = jnp.arange(m) + RESERVED_PAGES
    tgt = jnp.where(
        (jnp.arange(m) < n_valid) & (block_row[:m] != NULL_PAGE),
        block_row[:m], TRASH_PAGE)
    staged = iter(staged)
    return _attention_entries(
        tree, lambda layer: tuple(p.at[tgt].set(st[src]) for p, st in zip(layer, next(staged))))
