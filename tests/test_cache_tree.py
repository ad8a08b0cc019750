"""models/cache.py: the ONE write of an attention layer's rows into its cache
entry, for every entry kind under every addressing, against a NumPy oracle;
the state modules' pair; the tree operations over a tree with a state entry in
it; and the direction of the module's imports."""

import ast
import pathlib
import types

import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import cache as kvcache
from seldon_core_tpu.models.cache import (
    NULL_PAGE,
    PAD_POS,
    RESERVED_PAGES,
    TRASH_PAGE,
    StateEntry,
    dequantize_kv,
    gather_paged_view,
    put_state,
    quantize_kv,
    state_rows,
    write_rows,
)

KVH, HD, W = 2, 4, 8          # W = KVH * HD: a flat row, and the latent row
MAX_LEN, PAGES, PS = 16, 10, 4
BF16 = jnp.bfloat16

KINDS = ("bf16_split", "bf16_flat", "int8", "latent")
ADDRESSINGS = ("dense_offset", "dense_vector", "dense_positions", "paged_token", "paged_chunk")


def _entry(kind, lead, rng):
    """An entry of ``kind`` with leading dims ``lead``, full of noise (so a row
    no write names is seen to stay), its positions a mix of real and empty."""
    def values(shape, dtype):
        if dtype == jnp.int8:
            return jnp.asarray(rng.integers(-128, 128, lead + shape), jnp.int8)
        return jnp.asarray(rng.normal(size=lead + shape), dtype)

    pos = np.where(rng.random(lead) < 0.5, rng.integers(0, 64, lead), PAD_POS).astype(np.int32)
    if len(lead) == 2 and lead[0] == PAGES:
        pos[NULL_PAGE] = PAD_POS          # the null page's invariant
    arrays = {
        "bf16_split": [((KVH, HD), BF16)] * 2,
        "bf16_flat": [((W,), BF16)] * 2,
        "int8": [((KVH, HD), jnp.int8), ((KVH,), jnp.float32)] * 2,
        "latent": [((W,), BF16)],
    }[kind]
    return tuple(values(shape, dtype) for shape, dtype in arrays) + (jnp.asarray(pos),)


def _rows(kind, b, s, rng):
    """What the layer's writer hands ``write_rows``: float32 K and V (the
    write casts them), int8 quantised by the caller, one latent row."""
    if kind == "latent":
        return (jnp.asarray(rng.normal(size=(b, s, W)), jnp.float32),)
    k, v = (jnp.asarray(rng.normal(size=(b, s, KVH, HD)), jnp.float32) for _ in range(2))
    if kind == "int8":
        return (*quantize_kv(k), *quantize_kv(v))
    return k, v


def _call(addressing):
    """(b, s, positions [b, s], kwargs of write_rows) of a call shape."""
    pad = PAD_POS
    if addressing == "dense_offset":      # prefill: one offset, padded columns written too
        return np.array([[3, 4, 5, pad], [3, 4, pad, pad]]), dict(cache_index=3)
    if addressing == "dense_vector":      # a token a sequence at its own offset
        return np.array([[2], [9]]), dict(cache_index=np.array([2, 9]))
    if addressing == "dense_positions":   # the verify: rows at their positions, PAD dropped
        return (np.array([[5, 6, pad, pad], [0, 1, 2, pad]]),
                dict(cache_index=np.array([5, 0])))
    if addressing == "paged_token":       # a NULL entry, a position past the table, padding
        tables = np.array([[5, 3, NULL_PAGE], [2, 4, 6]])
        return np.array([[3, 4, 9], [11, 12, pad]]), dict(block_tables=tables)
    # one sequence's run of two pages' rows from the MIDDLE of its second page,
    # its tail padding, its last table entry unallocated
    tables = np.array([[5, 3, 7, NULL_PAGE]])
    return (np.array([[6, 7, 8, 9, 10, 11, pad, pad]]), dict(block_tables=tables))


def _oracle(entry, rows, positions, block_tables=None, cache_index=None):
    """The entry after the write, array by array, in NumPy loops."""
    out = [np.array(a) for a in entry]
    new = [np.asarray(r.astype(a.dtype)).reshape(positions.shape + a.shape[2:])
           for a, r in zip(entry[:-1], rows)] + [positions.astype(np.int32)]
    b, s = positions.shape
    for i in range(b):
        for j in range(s):
            p = int(positions[i, j])
            if block_tables is not None:
                page = block_tables[i, p // PS] if p // PS < block_tables.shape[1] else NULL_PAGE
                at = (TRASH_PAGE if page == NULL_PAGE else page, p % PS)
            elif np.ndim(cache_index) == 0:
                at = (i, cache_index + j)
            elif s == 1:
                at = (i, cache_index[i])
            elif p < MAX_LEN:
                at = (i, p)
            else:
                continue
            for a, r in zip(out, new):
                a[at] = r[i, j]
    return out


@pytest.mark.parametrize("addressing", ADDRESSINGS)
@pytest.mark.parametrize("kind", KINDS)
def test_write_rows_holds_the_rows_at_their_positions(kind, addressing):
    rng = np.random.default_rng(KINDS.index(kind) * 7 + ADDRESSINGS.index(addressing))
    positions, how = _call(addressing)
    b, s = positions.shape
    paged = "block_tables" in how
    entry = _entry(kind, (PAGES, PS) if paged else (2, MAX_LEN), rng)
    rows = _rows(kind, b, s, rng)
    if paged:
        # whole pages where the rule says so: flat rows and a run of a page or more
        by_page = addressing == "paged_chunk" and kind in ("bf16_flat", "latent")
        assert kvcache.paged_write_by_page(entry, b, s) is by_page
    got = write_rows(entry, rows, jnp.asarray(positions),
                     **{k: jnp.asarray(v) for k, v in how.items()})
    want = _oracle(entry, rows, positions, **how)
    assert len(got) == len(entry)
    keep = np.arange(PAGES) != TRASH_PAGE if paged else slice(None)   # trash may differ
    for g, w, old in zip(got, want, entry):
        assert g.dtype == old.dtype and g.shape == old.shape
        np.testing.assert_array_equal(np.asarray(g)[keep], w[keep])
    if paged:
        np.testing.assert_array_equal(np.asarray(got[-1])[NULL_PAGE], PAD_POS)

    # the view the read takes holds each live row at its position
    tables = how.get("block_tables")
    if kind == "latent":
        view = got[0][jnp.asarray(tables)].reshape(b, -1, W) if paged else got[0]
        views, wrote = [np.asarray(view, np.float32)], [np.asarray(rows[0].astype(BF16), np.float32)]
    else:
        k_all, v_all, _ = (gather_paged_view(got, jnp.asarray(tables), jnp.float32, KVH)
                           if paged else kvcache.dense_view(got, jnp.float32))
        if kind == "int8":
            wrote = [dequantize_kv(rows[0], rows[1], jnp.float32),
                     dequantize_kv(rows[2], rows[3], jnp.float32)]
        else:
            wrote = [r.astype(BF16).astype(jnp.float32) for r in rows]
        views = [np.asarray(k_all, np.float32), np.asarray(v_all, np.float32)]
        wrote = [np.asarray(w) for w in wrote]
    seen = 0
    for i in range(b):
        for j in range(s):
            p = int(positions[i, j])
            if addressing == "dense_offset":
                p = how["cache_index"] + j
            elif p == PAD_POS or (paged and (p // PS >= tables.shape[1]
                                             or tables[i, p // PS] == NULL_PAGE)):
                continue
            for view, w in zip(views, wrote):
                np.testing.assert_array_equal(view[i, p].reshape(-1), w[i, j].reshape(-1))
            seen += 1
    assert seen >= 2


def _state_cfg(kind):
    """A ``cfg`` by duck type: what models/cache.py reads of one, no more."""
    return types.SimpleNamespace(
        n_layers=1, state_layers=(0,), layer_kind=lambda i: kind, dtype=BF16,
        conv_L_cache=3, dim=8, linear_conv_kernel_dim=4, linear_num_key_heads=1,
        linear_key_head_dim=4, linear_num_value_heads=2, linear_value_head_dim=4)


@pytest.mark.parametrize("by_slot", (False, True), ids=("own_rows", "by_slot"))
@pytest.mark.parametrize("kind", ("conv", "linear_attention"))
def test_state_rows_and_put_state(kind, by_slot):
    rng = np.random.default_rng(3)
    slots = 4
    n = {"conv": 1, "linear_attention": 2}[kind]
    (zeros,) = kvcache._with_state_entries(_state_cfg(kind), [], slots)
    assert kvcache.is_state_entry(zeros) and len(zeros) == n
    assert zeros[-1].dtype == (BF16 if kind == "conv" else jnp.float32)
    entry = StateEntry(jnp.asarray(rng.normal(size=a.shape), a.dtype) for a in zeros)
    assert kvcache.state_nbytes([entry, (jnp.zeros(3),)]) == sum(a.nbytes for a in entry)

    named = jnp.asarray([2], jnp.int32) if by_slot else None
    read = state_rows(entry, named, n)
    assert len(read) == n
    for a, r in zip(entry, read):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(a[2:3] if by_slot else a))
    # the layer hands back float32 rows: the entry keeps its dtypes
    new = tuple(jnp.asarray(rng.normal(size=r.shape), jnp.float32) for r in read)
    put = put_state(entry, named, new)
    assert kvcache.is_state_entry(put) and len(put) == n
    for a, p, w in zip(entry, put, new):
        assert p.dtype == a.dtype and p.shape == a.shape
        want = np.array(a)
        want[slice(2, 3) if by_slot else slice(None)] = np.asarray(w.astype(a.dtype))
        np.testing.assert_array_equal(np.asarray(p), want)   # a slot not named: bit for bit

    # without a cache: from zeros (None each), and the new arrays are the entry
    assert state_rows(None, None, n) == (None,) * n
    fresh = put_state(None, None, new)
    assert kvcache.is_state_entry(fresh) and all(f is w for f, w in zip(fresh, new))


def test_tree_operations_hand_a_state_entry_on():
    rng = np.random.default_rng(5)
    state = StateEntry((jnp.ones((3, 2, 8), BF16),))
    tree = [state, _entry("bf16_flat", (PAGES, PS), rng), _entry("bf16_flat", (PAGES, PS), rng)]
    assert kvcache.first_paged(tree) is tree[1]

    ids = jnp.asarray([4, 6, TRASH_PAGE])
    reset = kvcache.reset_pages(tree, ids)
    want = np.array(tree[1][-1])
    want[[4, 6, TRASH_PAGE]] = PAD_POS
    np.testing.assert_array_equal(np.asarray(reset[1][-1]), want)
    assert reset[0] is state and reset[1][0] is tree[1][0]

    tables = jnp.asarray([[5, 3, NULL_PAGE], [2, 4, 6]])
    gone = kvcache.forget_positions(tree, jnp.asarray([[5, PAD_POS], [PAD_POS, 9]]), tables)
    want = np.array(tree[2][-1])
    want[3, 1] = want[6, 1] = PAD_POS
    keep = np.arange(PAGES) != TRASH_PAGE
    np.testing.assert_array_equal(np.asarray(gone[2][-1])[keep], want[keep])
    assert gone[0] is state
    dense = [state, _entry("latent", (2, MAX_LEN), rng)]
    gone = kvcache.forget_positions(dense, jnp.asarray([[5, PAD_POS], [PAD_POS, 9]]))
    want = np.array(dense[1][-1])
    want[0, 5] = want[1, 9] = PAD_POS
    np.testing.assert_array_equal(np.asarray(gone[1][-1]), want)

    copied = kvcache.cow_page_copy(tree, 3, 8, 2)
    np.testing.assert_array_equal(np.asarray(copied[1][0][8]), np.asarray(tree[1][0][3]))
    np.testing.assert_array_equal(
        np.asarray(copied[1][-1][8]), np.r_[np.asarray(tree[1][-1][3, :2]), [PAD_POS] * (PS - 2)])
    assert copied[0] is state

    # two sequence pages out to a bucket and into another pool: state stays home
    idx = jnp.asarray([NULL_PAGE, TRASH_PAGE, 5, 3])
    bucket = kvcache.export_pages(tree, idx)
    assert len(bucket) == 2 and bucket[0][0].shape == (4, PS, W)
    there = [state, _entry("bf16_flat", (PAGES, PS), rng), _entry("bf16_flat", (PAGES, PS), rng)]
    row = jnp.asarray([7, 9, NULL_PAGE])
    landed = kvcache.import_pages(there, bucket, row, 2, 2)
    assert landed[0] is state
    for layer in (1, 2):
        for got, src, old in zip(landed[layer], tree[layer], there[layer]):
            want = np.array(old)
            want[7], want[9] = np.asarray(src[5]), np.asarray(src[3])
            np.testing.assert_array_equal(np.asarray(got), want)
    assert RESERVED_PAGES == 2


def _window_cfg(**more):
    base = dict(n_layers=4, n_kv_heads=KVH, head_dim=HD, dtype=BF16, kv_lora_rank=0,
                kv_cache_dtype="bf16", kv_rows_flat=True, state_layers=(),
                window_layers=(1, 2, 3), sliding_window=8)
    base.update(more)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("window_layers,kvd,full,window", [
    # (K + V rows of KVH x HD values + an int32 position) a layer of the class
    ((1, 2, 3), "bf16", 1 * (2 * W * 2 + 4), 3 * (2 * W * 2 + 4)),
    ((1, 2, 3), "int8", 1 * (2 * (W + KVH * 4) + 4), 3 * (2 * (W + KVH * 4) + 4)),
    ((), "bf16", 4 * (2 * W * 2 + 4), 0),
])
def test_bytes_a_token_and_pool_lengths_by_page_class(window_layers, kvd, full, window):
    cfg = _window_cfg(window_layers=window_layers)
    per_token = kvcache.kv_cache_bytes_per_token
    assert per_token(cfg, kvd, "full") == full and per_token(cfg, kvd, "window") == window
    assert per_token(cfg, kvd) == full + window       # every class, as it always was
    if kvd == "int8":
        return
    tree = kvcache.init_paged_kv_caches(cfg, PAGES, PS, kvd, window_pages=6)
    for i, layer in enumerate(tree):
        assert kvcache.is_window_entry(layer) == (i in window_layers)
        assert layer[0].shape == ((6 if i in window_layers else PAGES), PS, W)
        assert np.all(np.asarray(layer[-1]) == PAD_POS)
    if window_layers:
        with pytest.raises(ValueError, match="window_pages"):
            kvcache.init_paged_kv_caches(cfg, PAGES, PS, kvd)
    # the DENSE tree has no classes: every row stays, the mask alone has the bound
    assert not any(kvcache.is_window_entry(e) for e in kvcache.init_kv_caches(cfg, 2, MAX_LEN))


@pytest.mark.parametrize("window,widest,page,pages", [
    (4096, 512, 64, 73), (4096, 256, 64, 69), (12, 8, 4, 6), (32, 16, 8, 7), (10, 8, 4, 6)])
def test_pages_of_the_window_class_a_slot_can_hold(window, widest, page, pages):
    """A window, the widest call's rows and one page of rounding, in whole pages:
    no call can need more at once, wherever its first row lies in a page."""
    assert kvcache.window_slot_pages(window, widest, page) == pages
    for p0 in range(0, 3 * page):       # the pages [p0 - window + 1, p0 + widest - 1] touches
        first, last = max(p0 - window + 1, 0) // page, (p0 + widest - 1) // page
        assert last - first + 1 <= pages


def test_the_writes_and_the_reset_keep_a_window_entry_and_its_class():
    rng = np.random.default_rng(6)
    full = _entry("bf16_flat", (PAGES, PS), rng)
    windowed = kvcache.WindowEntry(_entry("bf16_flat", (6, PS), rng))
    tree = [full, windowed]
    # each class's ids name ITS pool's pages
    reset = kvcache.reset_pages(tree, jnp.asarray([4, TRASH_PAGE]), jnp.asarray([5, TRASH_PAGE]))
    assert kvcache.is_window_entry(reset[1]) and not kvcache.is_window_entry(reset[0])
    for got, old, ids in ((reset[0], full, [4, TRASH_PAGE]), (reset[1], windowed, [5, TRASH_PAGE])):
        want = np.array(old[-1])
        want[ids] = PAD_POS
        np.testing.assert_array_equal(np.asarray(got[-1]), want)
    alone = kvcache.reset_pages(tree, None, jnp.asarray([3]))
    assert alone[0] is full and np.all(np.asarray(alone[1][-1][3]) == PAD_POS)
    untouched = kvcache.reset_pages(tree, jnp.asarray([3]))
    assert untouched[1] is windowed
    # a token scatter and a whole-page write both hand a WindowEntry back
    for positions, tables in ((np.array([[3, 4, 9]]), np.array([[5, 3, NULL_PAGE]])),
                              (np.array([[4, 5, 6, 7, 8, 9, PAD_POS, PAD_POS]]),
                               np.array([[NULL_PAGE, 3, 4, NULL_PAGE]]))):
        b, s = positions.shape
        rows = _rows("bf16_flat", b, s, rng)
        got = write_rows(windowed, rows, jnp.asarray(positions), block_tables=tables)
        assert kvcache.is_window_entry(got)
        want = _oracle(windowed, rows, positions, block_tables=tables)
        keep = np.arange(6) != TRASH_PAGE
        for a, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a)[keep], w[keep])


def test_cache_module_imports_point_one_way():
    source = pathlib.Path(kvcache.__file__).read_text()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "no relative import: the check reads absolute names"
            imported.add(node.module)
            imported.update(f"{node.module}.{alias.name}" for alias in node.names)
    banned = ("seldon_core_tpu.models.transformer", "seldon_core_tpu.runtime",
              "seldon_core_tpu.servers")
    assert not [m for m in imported if m.startswith(banned)], imported
    # and the ones that used to own the form import it
    root = pathlib.Path(kvcache.__file__).parents[1]
    for user in ("models/transformer.py", "runtime/batcher.py", "servers/llmserver.py",
                 "runtime/disagg.py"):
        assert "seldon_core_tpu.models.cache" in (root / user).read_text() or \
            "from seldon_core_tpu.models import cache" in (root / user).read_text(), user
