"""The flash kernels' block choice and index clamping, in pure Python:
nothing here runs a kernel.

``flash_blocks`` picks (block_q, block_k) from the shapes alone; the
forward and dq grids take one KV head's whole GQA group per step, and
the index maps of all three sweeps clamp dead causal/window steps onto a
live step's block so the pipeline copies nothing for them.
"""
import itertools

import pytest

from repro.kernels.flash_attention import (VMEM_BUDGET, block_live,
                                           flash_blocks, flash_vmem_bytes,
                                           kv_block_index, q_block_index)

LENGTHS = (1, 64, 80, 128, 129, 200, 256, 384, 512, 640, 900, 1024, 1536,
           2048, 3000, 4096, 8192)


def _padded(n: int) -> int:
    """The length the wrapper pads to with 128-row blocks."""
    return n if n <= 128 else -(-n // 128) * 128


@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("n", LENGTHS)
def test_blocks_tile_the_128_padded_length(n, G):
    """Each block tiles its length at its 128-padding, so no sequence is
    padded further than with 128-row blocks."""
    bq, bk = flash_blocks(n, n, 128, G)
    for b in (bq, bk):
        assert _padded(n) % b == 0, (n, b)
        assert -(-n // b) * b == _padded(n), (n, b)
        assert b in (n, 128, 256, 512, 1024), b


@pytest.mark.parametrize("window", [1, 16, 100, 128, 129, 200, 300, 511,
                                    512, 4096])
@pytest.mark.parametrize("n", [256, 1024, 2048, 4096])
def test_block_k_within_the_window(n, window):
    bq, bk = flash_blocks(n, n, 128, 4, window)
    assert bk <= -(-window // 128) * 128, (window, bk)
    assert _padded(n) % bk == 0


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("D", [128, 256])   # 64 and 72 pad to 128
@pytest.mark.parametrize("n", [128, 256, 512, 1024, 2048, 4096, 8192])
def test_blocks_fit_the_vmem_budget(n, D, G, window):
    bq, bk = flash_blocks(n, n, D, G, window)
    assert flash_vmem_bytes(bq, bk, D, G) <= VMEM_BUDGET
    assert VMEM_BUDGET < 16 << 20          # v5e's scoped VMEM


def test_blocks_grow_where_the_budget_allows():
    """Larger blocks than 128 wherever they fit: the cell's shapes take
    256×1024, the squarer 512×512 needs more than the budget."""
    assert flash_blocks(1024, 1024, 128, 4) == (256, 1024)
    assert flash_vmem_bytes(512, 512, 128, 4) > VMEM_BUDGET
    assert flash_blocks(1024, 1024, 128, 1) == (512, 1024)
    assert flash_blocks(64, 64, 128, 4) == (64, 64)
    assert flash_blocks(384, 384, 128, 4) == (128, 128)


def test_cell_grids_hold_an_eighth_of_the_steps():
    """granite-3-2b.k2-h4: K=2 vmapped replicas × 4 sequences of 1024,
    32 query / 8 KV heads, head_dim 64 padded to 128. The per-head grid
    with 128 blocks took 8 · 32 · 8 · 8 = 16,384 steps a call."""
    B, S, HQ, HKV, D = 2 * 4, 1024, 32, 8, 128
    before = B * HQ * (S // 128) * (S // 128)
    assert before == 16_384
    bq, bk = flash_blocks(S, S, D, HQ // HKV)
    steps = B * HKV * (S // bq) * (S // bk)    # forward and dq alike
    assert steps <= before // 8, (bq, bk, steps)


def _sweep_copies(index, n_outer, n_inner, live):
    """Blocks copied over a sweep: one per change of the fetched index
    (the pipeline skips a step whose block index repeats), and checks
    that every live step fetches its own block."""
    copies, prev = 0, None
    for o in range(n_outer):
        for i in range(n_inner):
            idx = int(index(o, i))
            assert 0 <= idx < n_inner
            if live(o, i):
                assert idx == i, (o, i, idx)
            copies += idx != prev
            prev = idx
    return copies


@pytest.mark.parametrize("causal,window", [(True, None), (True, 200),
                                           (True, 1), (False, 300)])
@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 1024), (512, 256),
                                   (128, 512)])
def test_dead_steps_fetch_nothing(bq, bk, causal, window):
    """The clamped index maps: a live step fetches its block, a dead one
    repeats the first or last live block of its band, so a sweep copies
    at most one block per live step, where unclamped it copies one per
    step."""
    S = T = 2048
    nq, nk = S // bq, T // bk
    band = dict(block_q=bq, block_k=bk, causal=causal, window=window)

    def live(i, j):
        return bool(block_live(i * bq, j * bk, bq, bk, causal, window))

    n_live = sum(live(i, j) for i, j in itertools.product(range(nq),
                                                          range(nk)))
    kv = _sweep_copies(lambda i, j: kv_block_index(i, j, nk=nk, **band),
                       nq, nk, live)
    q = _sweep_copies(lambda j, i: q_block_index(j, i, nq=nq, **band),
                      nk, nq, lambda j, i: live(i, j))
    assert kv <= n_live and q <= n_live
    if n_live < nq * nk:
        assert kv < nq * nk and q < nq * nk
