"""The kernels' live-block table (``kernels/tree_attention.live_table``):
the live pairs of ``block_live_mask`` in the order the kernels accumulate
in, one write-only entry for each output block with no live pair, first
and last flags that open and close each output block, and the dynamic
count the grid runs to.  Checked on a packed tree row, an all-padding
row, a partition-gateway layout and a sliding window, in the forward's
(b, q-block, kv-block) order and the dk/dv kernel's (b, kv-block,
q-block) order."""
import jax.numpy as jnp
import numpy as np
import pytest

from test_kernels import _gateway_meta, _tree_meta

from repro.kernels.tree_attention import (block_live_mask, entry_blocks,
                                         entry_flags, live_table)

S, BLOCK = 256, 32


def _layout(case):
    """(kv_last [B, Skv], q_off, pos_q, pos_k, window) of one case."""
    if case == "packed":
        kv_last, _ = _tree_meta(5, 2, S)
        return kv_last, 0, None, None, None
    if case == "padding_row":
        kv_last, _ = _tree_meta(6, 2, S)
        kv_last = kv_last.at[1].set(-1)
        return kv_last, 0, None, None, None
    if case == "gateway":
        A = 256
        kv_last, *_ = _gateway_meta(7, 2, S, A, pad_rows=(0, 96))
        return kv_last, A, None, None, None
    kv_last, pos = _tree_meta(8, 2, S)
    return kv_last, 0, pos, pos, 5


def _mask(case):
    kv_last, q_off, pos_q, pos_k, window = _layout(case)
    return block_live_mask(kv_last, S, BLOCK, BLOCK, q_off=q_off,
                           pos_q=pos_q, pos_k=pos_k, window=window)


def _expected(live):
    """The kernels' entries, written out: (b, x, y, live, first, last)."""
    out = []
    for b in range(live.shape[0]):
        for x in range(live.shape[1]):
            ys = np.flatnonzero(live[b, x])
            if ys.size == 0:
                out.append((b, x, 0, False, True, True))
            for i, y in enumerate(ys):
                out.append((b, x, int(y), True, i == 0, i == ys.size - 1))
    return out


def _entries(live):
    table, n = live_table(live)
    table, n = np.asarray(table), int(n)
    got = [tuple(int(f) for f in entry_blocks(c))
           + tuple(bool(f) for f in entry_flags(c)) for c in table[:n]]
    return table, n, got


CASES = ["packed", "padding_row", "gateway", "window"]
ORDERS = {"fwd_dq": lambda m: m, "dkv": lambda m: m.transpose(0, 2, 1)}


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("case", CASES)
def test_table_is_the_masks_live_pairs_in_kernel_order(case, order):
    live = ORDERS[order](_mask(case))
    host = np.asarray(live)
    _, _, got = _entries(live)
    assert got == _expected(host)
    # the host mask (the planner's counters) is the same predicate
    kv_last, q_off, pos_q, pos_k, window = _layout(case)
    host_mask = block_live_mask(
        np.asarray(kv_last), S, BLOCK, BLOCK, q_off=q_off,
        pos_q=None if pos_q is None else np.asarray(pos_q),
        pos_k=None if pos_k is None else np.asarray(pos_k), window=window)
    assert isinstance(host_mask, np.ndarray)
    np.testing.assert_array_equal(ORDERS[order](host_mask), host)


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("case", CASES)
def test_every_output_block_opens_and_closes_once(case, order):
    live = ORDERS[order](_mask(case))
    _, _, got = _entries(live)
    B, X = live.shape[:2]
    for flag in (4, 5):                      # first, last
        seen = [(b, x) for b, x, *_ in (e for e in got if e[flag])]
        assert sorted(seen) == [(b, x) for b in range(B) for x in range(X)]
    # an output block's entries are consecutive (TPU output revisiting)
    runs = [(b, x) for b, x, *_ in got]
    assert runs == sorted(runs)


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("case", CASES)
def test_count_is_live_pairs_plus_write_only_entries(case, order):
    live = np.asarray(ORDERS[order](_mask(case)))
    _, n = live_table(jnp.asarray(live))
    empty = int((~live.any(-1)).sum())
    assert int(n) == int(live.sum()) + empty
    assert int(n) <= live.size
    if case == "padding_row":
        assert not live[1].any() and empty >= live.shape[1]
