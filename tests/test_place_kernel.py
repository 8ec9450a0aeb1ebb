"""The placement kernel (ops/record.py ``_place_kernel``), INTERPRETED,
against the XLA reference placement the CPU grower runs (``_xla_place``)
and a numpy stable partition, bit for bit on the record and its leaf-id
row.  ``place_runs`` runs the reference off the chip, so these call the
kernel's own builder, ``_place_call``, with ``interpret=True``.

The compacted tiles are built here from a random ``go`` vector, their
lanes past each run left as random garbage: a lane the kernel should not
take shows.  One record row holds the column ids, so a window that is
the numpy partition's has every row written exactly once, and nothing
outside ``[begin, begin + pcnt)`` moved (the old step table's own test,
``test_place_table_live_steps_are_a_prefix``, checked that on the table;
the table is gone).  On the chip: analysis/kernel_parity.py check_place.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu.ops.record as R
from lightgbm_tpu.analysis.kernel_parity import _np_partition
from lightgbm_tpu.learners import fused

T = R.TILE
NT = 4  # tiles of the window's buffers
CAP = NT * T
N_PAD = CAP + 3 * T
LEFT, RIGHT = 3, 5  # the children's leaf ids

_BEGINS = {"0": T, "mid": T + T // 2 + 3, "T-1": 2 * T - 1}  # begin % T
_PCNTS = {"full": CAP - 5, "live<nt": 2 * T + 10, "small": 100}


def _nleft_go(kind, begin, pcnt, rng):
    """A window-relative go vector whose left count is of ``kind``."""
    lane = np.arange(CAP)
    if kind == "0":
        go = np.zeros(CAP, bool)
    elif kind == "pcnt":
        go = np.ones(CAP, bool)
    elif kind == "aligned":  # the rights start a block of their own
        go = lane < (-begin) % T + T
    else:  # "shared": lefts end inside the block the rights start in
        go = rng.rand(CAP) < 0.4
    go &= lane < pcnt
    return go


def _case(W, begin, pcnt, kind, seed=0):
    """(rec, comp, cl, cr, go) for one window: comp as the split step
    lays it out, garbage past each run."""
    rng = np.random.RandomState(seed)
    rec = rng.randint(-2**31, 2**31 - 1, (W, N_PAD)).astype(np.int32)
    rec[W - 5] = np.arange(N_PAD)  # column ids
    go = _nleft_go(kind, begin, pcnt, rng)
    if kind == "shared" and 0 < go.sum() < pcnt:
        assert (begin + go.sum()) % T
    comp = rng.randint(-2**31, 2**31 - 1, (NT, W, 2 * T)).astype(np.int32)
    valid = np.arange(CAP) < pcnt
    for j in range(NT):
        cols = slice(j * T, (j + 1) * T)
        tile, g, v = rec[:, begin:begin + CAP][:, cols], go[cols], valid[cols]
        lefts, rights = tile[:, g], tile[:, ~g & v]
        comp[j, :, :lefts.shape[1]] = lefts
        comp[j, :, T:T + rights.shape[1]] = rights
    cl = go.reshape(NT, T).sum(axis=1).astype(np.int32)
    cr = (np.clip(pcnt - np.arange(NT) * T, 0, T) - cl).astype(np.int32)
    return rec, comp, cl, cr, go


@functools.partial(jax.jit, static_argnames="leaf_row")
def _kernel(rec, comp, cl, begin, pcnt, nleft, do_split, live, leaf_row):
    return R._place_call(
        rec, comp, cl, R.place_scalars(begin, pcnt, nleft, do_split, LEFT,
                                       RIGHT, live),
        leaf_row=leaf_row, interpret=True)


@functools.partial(jax.jit, static_argnames="leaf_row")
def _reference(rec, comp, cl, cr, begin, pcnt, nleft, do_split, leaf_row):
    loff, roff = R._run_offsets(cl, cr)
    return R._xla_place(rec, comp, loff, roff, begin, pcnt, nleft, do_split,
                        CAP, leaf_row, jnp.int32(LEFT), jnp.int32(RIGHT))


def _place_both(W, begin, pcnt, kind, do_split):
    rec, comp, cl, cr, go = _case(W, begin, pcnt, kind)
    leaf_row = W - 4
    nleft = jnp.int32(go.sum())
    live = jnp.int32(max(1, -(-pcnt // T)))  # what the grower passes
    args = (jnp.int32(begin), jnp.int32(pcnt), nleft, jnp.bool_(do_split))
    got = np.asarray(_kernel(jnp.asarray(rec), jnp.asarray(comp),
                             jnp.asarray(cl), *args, live, leaf_row=leaf_row))
    ref = np.asarray(_reference(jnp.asarray(rec), jnp.asarray(comp),
                                jnp.asarray(cl), jnp.asarray(cr), *args,
                                leaf_row=leaf_row))
    return rec, go, leaf_row, got, ref


@pytest.mark.parametrize("kind", ["0", "pcnt", "shared", "aligned"],
                         ids=lambda k: f"nleft-{k}")
@pytest.mark.parametrize("pcnt", list(_PCNTS), ids=lambda p: f"pcnt-{p}")
@pytest.mark.parametrize("begin", list(_BEGINS), ids=lambda b: f"r{b}")
@pytest.mark.parametrize("W", [16, 32, 64, 512], ids=lambda w: f"W{w}")
def test_kernel_places_as_the_reference(W, begin, pcnt, kind):
    """Lefts then rights, each in its order, the children's ids in the
    leaf-id row, at begins of 0, mid-block and T - 1 into a block, with
    no lefts, no rights, lefts ending inside the rights' first block or
    at its start, over every tile, fewer live tiles than the buffers
    hold, and a window under one tile (inside one block, or across two
    at T - 1)."""
    b, p = _BEGINS[begin], _PCNTS[pcnt]
    rec, go, leaf_row, got, ref = _place_both(W, b, p, kind, True)
    assert got.tobytes() == ref.tobytes()
    want, nleft = _np_partition(rec, go, b, p, leaf_row, LEFT, RIGHT)
    assert nleft == go.sum()
    # the column ids of the window are a permutation of it: every row
    # written once; and nothing outside the window moved
    np.testing.assert_array_equal(got, want)
    assert sorted(got[W - 5, b:b + p]) == list(range(b, b + p))


@pytest.mark.parametrize("W", [16, 32, 64, 512], ids=lambda w: f"W{w}")
def test_no_split_writes_nothing(W):
    """``do_split`` false: the launch (one grid step) leaves the record
    as it was, as the reference does."""
    rec, _, _, got, ref = _place_both(W, _BEGINS["mid"], _PCNTS["full"],
                                      "shared", False)
    assert got.tobytes() == ref.tobytes() == rec.tobytes()


@pytest.mark.parametrize("bins", [15, 63, 127, 255, 511, 1023])
def test_placement_vmem_under_the_split_steps(bins):
    """The grower's gate (learners/fused.py chunking) reads the split
    step's VMEM sum: at every record height it admits, the placement's
    is no larger, so the gate holds for both launches."""
    k = 4 if bins <= 256 else 2
    Bp = R.round_up(bins, 128)
    gate = fused.chunking(1, bins).vmem_max
    admitted = 0
    for F in range(1, 8193, 7):
        W, Fp = R.rec_height(F, k), R.round_up(F, 8)
        split = R.split_step_vmem_bytes(Fp, Bp, W)
        if split > gate:
            break
        admitted += 1
        assert R.place_vmem_bytes(W) <= split, (F, W)
    assert admitted > 100
