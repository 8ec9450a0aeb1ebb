"""Compile the CHIP program with no chip.

Tier-1 runs on the CPU, where the library selects the canonical grower
(segment-sum histograms, the jax.numpy search); on a TPU it selects the
fused grower (learners/fused.py) — a different program, whose kernels
the CPU runs only interpreted.  libtpu can still compile it: select the
TPU paths (device.assume_platform), lower the selected grower for a
described v5e topology, and let Mosaic accept or refuse every kernel.  A refusal then fails here, not in a chip call.
This says nothing about what the kernels compute (chip_smoke.py does).
"""

import functools
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import device
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.learners import fused
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import record as R

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import kernel_bundles  # noqa: E402  (tools/)


@pytest.fixture(scope="module")
def llo_dir(tmp_path_factory):
    """Where this process's libtpu writes its final schedules, asked for
    before it loads (tools/kernel_bundles.py: two small files a kernel
    or fusion of every compile below)."""
    return kernel_bundles.enable(str(tmp_path_factory.mktemp("llo")))


@pytest.fixture(scope="module")
def topo(llo_dir):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal is a skip
        pytest.skip(f"libtpu gives no v5e topology here: {e}")


@pytest.fixture(scope="module")
def grower(topo):
    """``(lowered, compiled, jaxpr)`` of the selected grower for one
    v5e chip."""
    n, F = 100_000, 28
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=63, max_bin=255,
                 min_data_in_leaf=100)
    with device.assume_platform("tpu"):
        ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg)
        gbdt = GBDT(cfg, ds, create_objective(cfg, ds.metadata, n))
        grow = gbdt._grow  # functools.partial over the jitted grow_tree
        assert gbdt._grower == ("fused", "")
        assert grow.func is fused.grow_tree  # the chip's grower
        on_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.result_type(a), sharding=on_chip),
            (gbdt._bins_T, jnp.zeros(n, jnp.float32),
             jnp.zeros(n, jnp.float32), gbdt._bag_mask, jnp.ones(F, bool),
             gbdt._nbpf, gbdt._is_cat, gbdt._learner_params))
        traced = grow.func.trace(*args, **grow.keywords)
        lowered = traced.lower()
    # Mosaic refuses a kernel here, or not
    return lowered, lowered.compile(), traced.jaxpr.jaxpr


def test_serial_grower_compiles_for_v5e(grower):
    lowered, compiled, _ = grower
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    # the root histogram, ONE split step and one placement launch per
    # chunk of its step table (19 calls here when each capacity tier
    # had a kernel body of its own: PERF.md, PR 27)
    assert 3 <= mosaic_calls <= 8, mosaic_calls  # 3 at this shape
    # (no HBM temporaries are left to count at this shape: without the
    # tier conditionals XLA keeps the 12.8 MB record in VMEM)
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_scopes_and_kernel_names_in_the_compiled_grower(grower):
    """What obs/device_time reads back, and the harness's layer files
    match, as the chip's compiler leaves it: every scope the grower
    writes is in some ``op_name``, none is outside the table, and every
    Mosaic call's instruction is named after its kernel and capacity
    (an instruction takes the name of its innermost scope: read off
    compiled programs, not documented, hence this test)."""
    from lightgbm_tpu.obs import device_time as dt

    text = grower[1].as_text()
    found = {dt.scope_of(name) for name in
             set(re.findall(r'op_name="([^"]*)"', text))} - {None}
    scopes = {scope for scope, _ in found}
    assert scopes <= set(dt.SCOPE_NAMES), scopes - set(dt.SCOPE_NAMES)
    # the fused grower holds no _tier_chain: one launch pair a split at
    # a run-time tile count (lgbm.grow.tier stays in the table for the
    # canonical grower, which keeps the chain)
    assert scopes >= {s for s in dt.SCOPE_NAMES if ".grow." in s} - {
        "lgbm.grow.tier", "lgbm.grow.exchange"} | {
        "lgbm.histogram", "lgbm.split_step", "lgbm.partition",
        "lgbm.split_search", "lgbm.root_totals"}
    assert "lgbm.grow.tier" not in scopes
    assert "lgbm.grow.exchange" not in scopes  # one device: no collective
    calls = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
        text, re.M)
    assert 3 <= len(calls) <= 8, calls
    for name in calls:
        assert re.fullmatch(
            r"lgbm\.(split_step\.dyn|partition\.place\.dyn"
            r"|histogram\.cap\d+)(\.\d+)?", name), name
    assert sum(n.startswith("lgbm.split_step") for n in calls) == 1
    assert sum(n.startswith("lgbm.histogram.cap") for n in calls) == 1


def test_the_compiled_grower_is_the_program_the_cells_ran_at_pr29(grower):
    """PR 30 split ``grow_tree`` into two growers and moved nothing the
    chip runs: the compiled program at this shape has the parent's
    (``ea604a5``) three Mosaic calls, its one ``while`` (the split
    loop) and no ``conditional`` anywhere, in or out of the loop (its
    optimized HLO was the parent's instruction for instruction, 1,961 of
    them: PERF.md, PR 30).  A ``lax.cond`` that comes back into
    learners/fused.py shows here before it costs a record copy a split."""
    from lightgbm_tpu.obs import device_time as dt

    module = grower[1].runtime_executable().hlo_modules()[0]
    prog = dt.program_of_module(module.as_serialized_hlo_module_proto())
    count = {op: sum(ins.opcode == op for ins in prog.instrs.values())
             for op in ("while", "conditional")}
    assert count == {"while": 1, "conditional": 0}, count
    assert sum(ins.target == "tpu_custom_call"
               for ins in prog.instrs.values()) == 3
    with open(fused.__file__) as fh:
        source = fh.read()
    assert "lax.cond" not in source and "_tier_chain" not in source


def _eqns_under(jaxpr, conds=()):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    each with the ``cond`` equations it sits under, outermost first."""
    for eqn in jaxpr.eqns:
        yield eqn, conds
        inner = conds + (eqn,) if eqn.primitive.name == "cond" else conds
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_under(sub, inner)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    return (eqn for eqn, _ in _eqns_under(jaxpr))


def _refs_read(jaxpr, var):
    """The vars of ``jaxpr`` that ``var`` is computed from through ref
    reads (``get``), across the jaxprs its equations hold: an input of a
    loop's or a branch's jaxpr stands for its equation's operand."""
    made_by, outer = {}, {}
    for eqn in _eqns(jaxpr):
        made_by.update((v, eqn) for v in eqn.outvars)
        subs = [getattr(p, "jaxpr", p) for param in eqn.params.values()
                for p in (param if isinstance(param, (list, tuple))
                          else [param])]
        for sub in subs:
            if hasattr(sub, "invars"):
                ops = eqn.invars[len(eqn.invars) - len(sub.invars):]
                outer.update(zip(sub.invars, ops))
    reads, todo = set(), [var]
    while todo:
        v = todo.pop()
        while v in outer:
            v = outer[v]
        eqn = made_by.pop(v, None)
        if eqn is not None:
            if eqn.primitive.name == "get":
                ref = eqn.invars[0]
                while ref in outer:
                    ref = outer[ref]
                reads.add(ref)
            todo += [u for u in eqn.invars if not hasattr(u, "val")]  # no Literals
    return reads


def test_the_one_histogram_kernel_keeps_the_bins_row_in_the_lanes(grower):
    """The grower holds ONE standalone histogram call, and its body
    builds the one-hot transposed: every dot contracts the lane axis of
    the stat rows and the one-hot, and nothing in it has the ``[C, 1]``
    shape of a bins row turned onto the sublanes: that relayout, once a
    feature a chunk, made the root histogram three times the price (453
    -> 138 ms alone at 7.5M x 100: PERF.md, PR 29).  Since PR 37 the
    one-hot is 128 rows, a bin's low seven bits, and the two planes of
    256 bins ride the other operand (ops/pallas_histogram.py bin_sums:
    ``[16 * H, C]`` masked stat rows; the VPU building a ``[256, C]``
    one-hot was the body's bound).  Read from the traced program's
    jaxpr: the Mosaic body in the lowered text is serialized bytecode."""
    hist = [e for e in _eqns(grower[2]) if e.primitive.name == "pallas_call"
            and "lgbm.histogram.cap" in str(e.source_info.name_stack)]
    assert len(hist) == 1, [str(e.source_info.name_stack) for e in hist]
    bins_block = hist[0].params["grid_mapping"].block_mappings[0].block_shape
    C = bins_block[1].block_size
    body = list(_eqns(hist[0].params["jaxpr"]))
    shapes = {v.aval.shape for e in body for v in e.outvars}
    assert (C, 1) not in shapes, sorted(s for s in shapes if s[-1:] == (1,))
    dots = [e for e in body if e.primitive.name == "dot_general"]
    assert dots and all(
        e.params["dimension_numbers"] == (((1,), (1,)), ((), ()))
        and [v.aval.shape for v in e.invars] == [(32, C), (128, C)]
        for e in dots), [e.params["dimension_numbers"] for e in dots]


def test_the_split_step_sums_full_tiles_of_staged_rows(grower):
    """The split step's F-feature one-hot body is in the kernel ONCE,
    under one condition, and that condition reads the staging count (the
    kernel's SMEM scratch): it runs, in a loop of K turns, once for each
    tile the smaller child's compacted rows have filled, or on the rows
    left over at the search step, and not in the per-step branch that
    compacts the parent's tiles.  There, with the sibling's rows masked,
    it was 2.7 times the rows and 856 of the step's 1,141 ms a tree at
    7.5M x 100 (PERF.md, PR 31).  Read from the traced jaxpr, as the
    root kernel's shape is above."""
    from lightgbm_tpu.ops import record as R

    F, Bp, T = 28, 256, R.TILE
    step = [e for e in _eqns(grower[2]) if e.primitive.name == "pallas_call"
            and "lgbm.split_step.dyn" in str(e.source_info.name_stack)]
    assert len(step) == 1, [str(e.source_info.name_stack) for e in step]
    kernel = step[0].params["jaxpr"]
    body = [(e, conds) for e, conds in _eqns_under(kernel)
            if e.primitive.name == "dot_general"
            and [v.aval.shape for v in e.invars] == [
                (16 * (Bp // 128), T), (128, T)]]
    # one copy: a dot a feature of a word group (the loop over whole
    # groups of LOOP_WORDS words; 28 features are under one) and of the
    # words after the last whole group, one more for the padded features
    group = 4 * R.LOOP_WORDS
    assert len(body) == (group if F >= group else 0) + F % group + (
        R.round_up(F, 8) > F), len(body)
    assert all(e.params["dimension_numbers"] == (((1,), (1,)), ((), ()))
               for e, _ in body)
    # and no dot of the kernel is the old body's, a [Bp, T] one-hot
    assert not [e for e, _ in _eqns_under(kernel)
                if e.primitive.name == "dot_general"
                and e.invars[1].aval.shape == (Bp, T)]
    assert {len(conds) for _, conds in body} == {1}
    assert len({id(conds[0]) for _, conds in body}) == 1
    cond = body[0][1][0]
    # what the condition is computed from, back to the refs it reads
    reads = _refs_read(kernel, cond.invars[0])
    staged = [v for v in kernel.invars
              if "smem" in str(v.aval) and v.aval.shape == (2,)
              and v.aval.dtype == jnp.int32]
    assert len(staged) == 1 and staged[0] in reads, (
        [str(v.aval) for v in kernel.invars], [str(v.aval) for v in reads])
    # the per-tile branch holds the compaction's rolls and no such dot
    tile_work = {id(c) for e, conds in _eqns_under(kernel)
                 if e.primitive.name == "roll" for c in conds}
    assert tile_work and id(cond) not in tile_work


# 127 bins: one plane, the unsplit body; 511: uint16 bins, four planes
@pytest.mark.parametrize("bins", [127, 128, 255, 511])
def test_both_histogram_bodies_split_the_bin_into_planes(bins):
    """Both kernels' one-hot body at every ``Bp`` (traced alone, the
    chip's form; nothing compiles): the one-hot operand of every
    histogram dot is ``[128, lanes]``, the other ``[16 * H, lanes]`` with
    ``H = Bp // 128``, both contracting their lanes; at one plane
    (``max_bin`` <= 128) that operand is the sixteen stat rows
    themselves, the unsplit form, chosen from the static ``Bp`` alone."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    from lightgbm_tpu.ops import record as R

    F, n, k = 12, 4096, 4 if bins <= 256 else 2
    Fp, Bp, T = R.round_up(F, 8), R.round_up(bins, 128), R.TILE
    H = PH.onehot_planes(Bp)
    assert H == {127: 1, 128: 1, 255: 2, 511: 4}[bins]
    f32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32)  # noqa: E731
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)  # noqa: E731

    def step(hists, rec, scal_f, meta, i):
        return R.split_step_counted(
            hists, rec, i, i, i > 0, i, i, i > 3, i, i + 1, scal_f, meta,
            F=F, cap=n, k=k, interpret=False, live_tiles=i)

    traced = {
        PH.SINGLE_LEAF_CHUNK: jax.make_jaxpr(functools.partial(
            PH.histogram_single_leaf_raw, num_bins=bins, interpret=False))(
            jax.ShapeDtypeStruct(
                (F, n), jnp.uint8 if k == 4 else jnp.uint16),
            f32(n), f32(n), f32(n)),
        T: jax.make_jaxpr(step)(
            f32(8, Fp, 4, Bp), i32(R.rec_height(F, k), 2 * n), f32(16),
            i32(Fp, 4), i32()),
    }
    for lanes, jaxpr in traced.items():
        calls = [e for e in _eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        dots = [e for e in _eqns(calls[0].params["jaxpr"])
                if e.primitive.name == "dot_general"
                and e.invars[1].aval.dtype == jnp.bfloat16]
        # a dot a feature: the root kernel walks the padded ones too,
        # the step sums them once
        assert len(dots) == (F + (Fp > F) if lanes == T else Fp), len(dots)
        assert all(
            e.params["dimension_numbers"] == (((1,), (1,)), ((), ()))
            and [v.aval.shape for v in e.invars] == [
                (16 * H, lanes), (128, lanes)]
            and e.outvars[0].aval.dtype == jnp.float32 for e in dots), [
            [v.aval.shape for v in e.invars] for e in dots]
        made = {(v.aval.shape, str(v.aval.dtype))
                for e in _eqns(calls[0].params["jaxpr"]) for v in e.outvars}
        assert (lanes, 1) not in {shape for shape, _ in made}
        # the one-hot, and none as tall as the bin axis beside it
        assert ((128, lanes), "bfloat16") in made
        assert H == 1 or ((Bp, lanes), "bfloat16") not in made


def _reachable(prog, comps):
    """Ids of the computations ``comps`` and all they call."""
    seen, todo = set(), list(comps)
    while todo:
        comp = todo.pop()
        if comp not in seen:
            seen.add(comp)
            todo += [c for ins in prog.instrs.values() if ins.comp == comp
                     for c in ins.called]
    return seen


def test_record_and_hists_stay_in_the_loop_carry(grower):
    """The compiled split loop neither copies the record or ``hists``
    nor holds them in a ``conditional``'s result: they go loop carry >
    split step > placement > carry through aliased Mosaic calls.  A
    ``conditional`` round the kernels cost two whole-record copies a
    split, 55% of a tree at 7.5M x 100 (PERF.md, PR 26 and 27).  Read
    with obs/device_time's HLO reader from the executable's own module."""
    _the_carry_is_clean(grower[1], n=100_000, F=28, L=63)


def _the_carry_is_clean(compiled, n, F, L, searched=False):
    from lightgbm_tpu.obs import device_time as dt
    from lightgbm_tpu.ops import record as R

    W = R.rec_height(F, 4)
    n_rec = R.round_up(n, R.TILE * R.split_tiles(W))
    record = f"s32[{W},{2 * n_rec}]"
    hists = f"f32[{L},{R.round_up(F, 8)},4,256]"
    module = compiled.runtime_executable().hlo_modules()[0]
    prog = dt.program_of_module(module.as_serialized_hlo_module_proto())
    loops = [ins for ins in prog.instrs.values() if ins.opcode == "while"
             and (dt.scope_of(ins.op_name) or ("",))[0] == "lgbm.grow.loop"]
    assert len(loops) == 1, [ins.name for ins in loops]
    assert record in loops[0].shape and hists in loops[0].shape, (
        record, hists, loops[0].shape)  # the shapes looked for are the carry's
    inside = _reachable(prog, loops[0].called)
    body = [ins for ins in prog.instrs.values() if ins.comp in inside]
    assert len(body) > 100, len(body)  # the reader really read the body
    copies = [(ins.name, ins.shape) for ins in body
              if ins.opcode.startswith("copy")
              and ins.shape.strip("()").split(", ")[0] in (record, hists)]
    assert not copies, copies
    conds = [(ins.name, ins.shape) for ins in body
             if ins.opcode == "conditional"
             and (record in ins.shape or hists in ins.shape)]
    assert not conds, conds
    kernels = [ins for ins in body if ins.target == "tpu_custom_call"]
    assert {dt.scope_of(k.op_name)[0] for k in kernels} == {
        "lgbm.split_step", "lgbm.partition"}
    # one launch of the split step a split, or its two halves
    assert len(kernels) == (3 if searched else 2), [k.name for k in kernels]


# The one-chip grow program at the ``grower`` fixture's shape, its split
# step at four parent tiles a grid step with a staging ring: sha256 of
# the optimized HLO, the Mosaic kernels' bodies included, with the debug
# tables (file names, lines, stack frames), every op's metadata and the
# kernels' source locations cut.  A change to the one-chip program on
# purpose records its new digest here.
ONE_CHIP_GROW_PROGRAM = (
    "4e637c303f3bc32383993af1efc7aec62b7fcd18e4fa90d4c91596d8ce76a3ed")


def _kernel_asm(body: str) -> str:
    """A Mosaic call's serialized kernel as MLIR text with no source
    locations (the bytecode carries the path and line of every op)."""
    import base64

    from jaxlib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def program_digest(compiled) -> str:
    text = re.sub(r"metadata=\{[^}]*\}", "", compiled.as_text())
    if "StackFrames" in text:
        text = text[text.index("\n\n", text.index("StackFrames")):]
    text = re.sub(r'"body":"([^"]+)"', lambda m: '"body":"%s"' % (
        hashlib.sha256(_kernel_asm(m.group(1)).encode()).hexdigest()), text)
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_one_chip_program_is_its_parents(grower):
    """learners/fused.py's data-parallel path (``axis``) traces nothing
    on one device: the compiled one-chip program is the parent's, kernel
    for kernel and instruction for instruction."""
    assert program_digest(grower[1]) == ONE_CHIP_GROW_PROGRAM


@pytest.fixture(scope="module")
def sharded_grower(topo):
    """The data-parallel fused grower compiled for the four chips of a
    v5e 2x2 (parallel/data_parallel.py make_fused_data_parallel_grower):
    ``(compiled, rows a chip, features, leaves)``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lightgbm_tpu.learners.serial import TreeLearnerParams
    from lightgbm_tpu.parallel.data_parallel import (
        make_fused_data_parallel_grower)

    n, F, L = 4 * 16_384, 13, 31
    mesh = Mesh(np.asarray(topo.devices), ("row",))
    rows, whole = NamedSharding(mesh, P("row")), NamedSharding(mesh, P())

    def S(dims, dtype=jnp.float32, sharding=whole):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    params = TreeLearnerParams(*[S(())] * 5 + [S((), jnp.int32)])
    args = (S((F, n), jnp.uint8, NamedSharding(mesh, P(None, "row"))),
            S((n,), sharding=rows), S((n,), sharding=rows),
            S((n,), sharding=rows), S((F,), jnp.bool_), S((F,), jnp.int32),
            S((F,), jnp.bool_), params)
    with device.assume_platform("tpu"):
        grow = make_fused_data_parallel_grower(mesh, num_bins=255,
                                               max_leaves=L)
        lowered = grow.trace(*args).lower()
    return lowered.compile(), n // 4, F, L


def test_the_data_parallel_grower_compiles_for_four_v5e_chips(
        sharded_grower):
    """Mosaic takes both halves of the split step (the compaction and
    histogram launch, ``lgbm.split_step.dyn``, and the subtraction and
    search, ``lgbm.split_step.search``) beside the root kernel and the
    placement; the split loop holds ONE all-reduce, of the smaller
    child's ``[Fp, 4, Bp]`` block, and carries each chip's record and the
    summed ``hists`` with no copy, as the one-chip loop does."""
    from lightgbm_tpu.obs import device_time as dt

    compiled, rows, F, L = sharded_grower
    _the_carry_is_clean(compiled, n=rows, F=F, L=L, searched=True)
    text = compiled.as_text()
    calls = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
        text, re.M)
    assert sorted(re.sub(r"(\.\d+)+$", "", c) for c in calls) == [
        "lgbm.histogram.cap16384", "lgbm.partition.place.dyn",
        "lgbm.split_step.dyn", "lgbm.split_step.search"], calls
    module = compiled.runtime_executable().hlo_modules()[0]
    prog = dt.program_of_module(module.as_serialized_hlo_module_proto())
    loop = [ins for ins in prog.instrs.values() if ins.opcode == "while"]
    inside = _reachable(prog, loop[0].called)
    reduces = [ins for ins in prog.instrs.values()
               if ins.opcode.startswith("all-reduce")]
    assert [ins.shape for ins in reduces if ins.comp in inside] == [
        f"f32[{R.round_up(F, 8)},4,256]"], [
        (ins.name, ins.shape) for ins in reduces]
    assert all(dt.scope_of(ins.op_name)[0] == "lgbm.grow.exchange"
               for ins in reduces), [ins.op_name for ins in reduces]


def _shape(topo):
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=chip)


def _compile_kernels(topo, F, bins):
    """The root kernel and the split step with its placement, alone, at
    ``F`` columns of ``bins`` bins (uint16 bins, two to a record word,
    past 256) for a v5e; the step must still be ONE call."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    from lightgbm_tpu.ops import record as R

    shape = _shape(topo)
    n, L, k = 20_480, 8, 4 if bins <= 256 else 2
    W, Fp, Bp = R.rec_height(F, k), R.round_up(F, 8), R.round_up(bins, 128)
    PH.histogram_single_leaf_raw.lower(
        shape((F, n), jnp.uint8 if k == 4 else jnp.uint16), shape((n,)),
        shape((n,)), shape((n,)), num_bins=bins, interpret=False).compile()

    def step(hists, rec, scal_f, meta, i):
        hists, comp, nleft, res, cl, cr, rec, _ = R.split_step_counted(
            hists, rec, i, i, i > 0, i, i, i > 3, i, i + 1, scal_f, meta,
            F=F, cap=n, k=k, interpret=False, live_tiles=i)
        rec = R.place_runs(
            rec, comp, (cl, cr), i, i, nleft, i > 0, i, i + 1, cap=n,
            leaf_row=R.num_words(F, k) + 4, interpret=False, live_tiles=i)
        return hists, rec, res

    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
        shape((L, Fp, 4, Bp)), shape((W, 2 * n), jnp.int32), shape((16,)),
        shape((Fp, 4), jnp.int32), shape((), jnp.int32))
    assert lowered.as_text().count("tpu_custom_call") == 2
    lowered.compile()


# Past one feature chunk (256 columns at 256 bins; 264 is the first
# width the parent's split step refused), at a width that is no whole
# number of chunks, at epsilon-2000.train's, and ONE chunk of 512
# columns at 128 bins, whose 136-word record is past what the step held
# before (Mosaic's default refuses it).
@pytest.mark.parametrize("F,bins", [(264, 255), (1000, 255), (2000, 255),
                                    (512, 127)])
def test_wide_tables_compile_for_v5e(topo, F, bins):
    """The root kernel and the split step with its placement at 256
    bins, alone, for a table wider than one ``[Fc, 4, Bp]`` block: Mosaic
    takes them (the split step's accumulators of every chunk and the
    record's blocks under the ``vmem_limit_bytes`` that
    ``split_step_vmem_bytes`` derives, learners/fused.py ``chunking``
    admitting the width), and the step is still ONE call."""
    from lightgbm_tpu.ops import record as R

    Fp, Bp = R.round_up(F, 8), R.round_up(bins, 128)
    with device.assume_platform("tpu"):
        plan = fused.chunking(F, bins)
    assert plan.fits and plan.record_words == R.rec_height(F, 4) > 64
    assert plan.feature_chunks == -(-Fp * Bp // (1 << 16))
    assert plan.vmem_bytes > 16 << 20  # past Mosaic's default: asked for
    _compile_kernels(topo, F, bins)


# 256 bins: the accumulators of every chunk fill the VMEM first; 128:
# the record's blocks (the tallest record the gate admits); 512: uint16
# bins, two to a word, chunks of 128 features.
@pytest.mark.parametrize("bins", [255, 127, 511])
def test_the_widest_table_the_gate_admits_compiles_for_v5e(topo, bins):
    """``select_grower``'s gate (learners/fused.py ``chunking``) is a
    sum with given terms, not a compile: here its EDGE is compiled, the
    widest table it admits at each kind of bound, so no width it offers
    the fused grower reaches Mosaic to be refused there."""
    with device.assume_platform("tpu"):
        lo, hi = 256, 1 << 15  # admitted, refused
        assert fused.chunking(lo, bins).fits
        assert not fused.chunking(hi, bins).fits
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if fused.chunking(mid, bins).fits else (lo, mid)
        plan = fused.chunking(lo, bins)
    assert plan.vmem_max == 96 << 20 and plan.vmem_bytes > 90 << 20, plan
    assert lo > 2000 and plan.feature_chunks >= 12, plan
    _compile_kernels(topo, lo, bins)


# a width that is no multiple of 8 sublanes, bins that are no multiple of
# 128 lanes, and a table past one chunk
@pytest.mark.parametrize("F,B", [(28, 256), (12, 255), (300, 256)])
def test_the_standalone_search_compiles_for_v5e(topo, F, B):
    """``search2_pallas`` (the canonical and the data-parallel growers'
    search on a chip: no benchmark cell runs it) in its one layout, a
    ``[6, Fc, B]`` block a grid step."""
    from lightgbm_tpu.ops.pallas_search import search2_pallas

    shape = _shape(topo)
    search2_pallas.lower(
        shape((F, B, 3)), shape((F, B, 3)), *[shape(())] * 6,
        shape((), jnp.bool_), shape((F,), jnp.bool_),
        shape((F,), jnp.int32), shape((F,), jnp.bool_), *[shape(())] * 5,
        interpret=False).compile()


@pytest.fixture(scope="module")
def wide_grower(topo):
    """``(compiled, jaxpr)`` of ``jit_grow_tree`` at epsilon-2000.train's
    width (fewer rows and leaves), from abstract shapes."""
    from lightgbm_tpu.learners.serial import TreeLearnerParams

    shape = _shape(topo)
    F, n, L = 2000, 20_480, 15
    params = TreeLearnerParams(*[shape(())] * 5, shape((), jnp.int32))
    with device.assume_platform("tpu"):
        traced = fused.grow_tree.trace(
            shape((F, n), jnp.uint8), shape((n,)), shape((n,)), shape((n,)),
            shape((F,), jnp.bool_), shape((F,), jnp.int32),
            shape((F,), jnp.bool_), params, num_bins=255, max_leaves=L)
        return traced.lower().compile(), traced.jaxpr.jaxpr


def test_the_grower_at_2000_columns_keeps_its_carry(wide_grower):
    """``jit_grow_tree`` at epsilon-2000.train's width: eight feature
    chunks through the root kernel, the split step and its search, and
    still three Mosaic calls, one ``while``, no ``conditional``, and
    neither the 512-word record nor ``hists`` copied in the loop."""
    from lightgbm_tpu.obs import device_time as dt

    compiled = wide_grower[0]
    module = compiled.runtime_executable().hlo_modules()[0]
    prog = dt.program_of_module(module.as_serialized_hlo_module_proto())
    count = {op: sum(ins.opcode == op for ins in prog.instrs.values())
             for op in ("while", "conditional")}
    assert count == {"while": 1, "conditional": 0}, count
    assert sum(ins.target == "tpu_custom_call"
               for ins in prog.instrs.values()) == 3
    _the_carry_is_clean(compiled, n=20_480, F=2000, L=15)


def test_a_table_with_categorical_columns_compiles_for_v5e(topo):
    """``jit_grow_tree`` at airline-13.train's width (fewer rows and
    leaves): thirteen columns, six of them declared categorical, two with
    more categories than bins, so the record is SIXTEEN words (one trip
    of the kernels' LOOP_WORDS loop; the cells before it hold 32 to 512)
    and ``is_categorical`` is set where every other cell passes zeros.
    ``select_grower`` takes the fused grower, Mosaic takes its kernels,
    and the ``==`` routing and the one-vs-rest search ride the kernels
    the numerical cells run: three Mosaic calls, one ``while``, no
    ``conditional``, the record and ``hists`` never copied."""
    from lightgbm_tpu.obs import device_time as dt

    n, F, L = 20_480, 13, 15
    cats = [1, 2, 3, 6, 9, 10]
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    for j, card in zip(cats, (12, 31, 7, 29, 340, 340)):
        X[:, j] = rng.randint(0, card, n)
    y = (X[:, 0] + (X[:, 9] % 7 < 3) > 0.5).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=L, max_bin=255,
                 min_data_in_leaf=100)
    with device.assume_platform("tpu"):
        ds = BinnedDataset.from_matrix(X, Metadata(label=y), config=cfg,
                                       categorical_features=cats)
        gbdt = GBDT(cfg, ds, create_objective(cfg, ds.metadata, n))
        assert gbdt._grower == ("fused", "")
        assert gbdt._chunking.record_words == 16
        assert np.flatnonzero(np.asarray(gbdt._is_cat)).tolist() == cats
        # both overflowing columns: 254 kept and the others' bin
        assert np.asarray(gbdt._nbpf)[[9, 10]].tolist() == [255, 255]
        grow = gbdt._grow
        on_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.result_type(a), sharding=on_chip),
            (gbdt._bins_T, jnp.zeros(n, jnp.float32),
             jnp.zeros(n, jnp.float32), gbdt._bag_mask, jnp.ones(F, bool),
             gbdt._nbpf, gbdt._is_cat, gbdt._learner_params))
        compiled = grow.func.lower(*args, **grow.keywords).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    prog = dt.program_of_module(module.as_serialized_hlo_module_proto())
    count = {op: sum(ins.opcode == op for ins in prog.instrs.values())
             for op in ("while", "conditional")}
    assert count == {"while": 1, "conditional": 0}, count
    assert sum(ins.target == "tpu_custom_call"
               for ins in prog.instrs.values()) == 3
    _the_carry_is_clean(compiled, n=n, F=F, L=L)


@pytest.mark.parametrize("which,F", [("grower", 28), ("wide_grower", 2000)])
def test_the_split_step_permutes_a_tile_by_lane_gathers(request, which, F):
    """The split step's kernel computes its K tiles' permutations on rows
    of control words and applies them by lane gathers (ops/record.py
    _source_rows, _gather_lanes): its jaxpr holds ``gather`` equations
    of ``[W, 128]`` blocks, ten a half of each of the K tiles, and the
    only lane rotates of an operand as tall as the record are those that
    move whole runs (two for the direct read's unaligned ``[W, K *
    TILE]`` block, one a tile for the staging append); every other
    rotate is of the ``[2K, TILE]`` operand on which ONE prefix sum and
    ONE compress network serve the K tiles (two rows a tile).  Rolling
    and blending all ``W + 1`` rows through 18 stages cost a tile of 512
    words 22.7 us in the step where one tile's network costs 13.1
    (PERF.md, PR 35)."""
    from lightgbm_tpu.ops import record as R

    jaxpr = request.getfixturevalue(which)[-1]
    W, T, G = R.rec_height(F, 4), R.TILE, R.GATHER_LANES
    K = R.split_tiles(W)
    assert K == {28: 4, 2000: 1}[F]
    step = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"
            and "lgbm.split_step.dyn" in str(e.source_info.name_stack)]
    assert len(step) == 1, [str(e.source_info.name_stack) for e in step]
    body = list(_eqns(step[0].params["jaxpr"]))
    gathers = [e for e in body if e.primitive.name == "gather"]
    blocks = T // G
    assert len(gathers) == K * 2 * blocks * (blocks + 1) // 2, len(gathers)
    assert {tuple(v.aval.shape for v in e.invars) for e in gathers} == {
        ((W, G), (W, G, 1))}
    rolled = [e.invars[0].aval.shape for e in body
              if e.primitive.name == "roll"]
    assert sorted(s for s in rolled if s[0] == W) == sorted(
        [(W, K * T)] * 2 + [(W, T)] * K), rolled
    assert {s for s in rolled if s[0] != W} == {(2 * K, T)}, rolled


def _compaction_bundles(topo, llo_dir, tiles):
    kernel_bundles.read(llo_dir)  # what the compiles before this wrote
    kernel_bundles.compact(_shape(topo), 32, tiles).compile()
    found = kernel_bundles.read(llo_dir)
    calls = [n for name, n in found.items() if name.startswith("compact_tiles")]
    assert len(calls) == 1, found
    return calls[0]


@pytest.mark.parametrize("tiles", [1, R.split_tiles(32)])
def test_the_compaction_is_scheduled_well_under_the_parents_bundles(
        topo, llo_dir, tiles):
    """tools/kernel_bundles.py reads libtpu's final schedule of the
    compaction (``_compact_tiles``) alone at the tall cells' 32 words:
    one tile under 1,000 bundles where rolling the whole tile through the
    networks took 1,198 (PERF.md, PR 35: at 512 words the parent's count
    came within 2% of the chip's time; at 32 the chip waits on the
    rotate unit, which no schedule shows); and at the K tiles a grid
    step the split step takes at 32 words, whose prefix sum and compress
    network serve all K in the vregs one used, at most three quarters of
    one tile's bundles a parent tile (737 and 320 when K = 4 came in:
    PERF.md section 6).  A form that spills, or a multiply where a select
    stood, or a network a tile, shows here at no chip time."""
    one = _compaction_bundles(topo, llo_dir, 1)
    assert 100 < one < 1000, one
    if tiles > 1:
        assert _compaction_bundles(topo, llo_dir, tiles) / tiles <= 0.75 * one


# (tools/kernel_bundles.py's kernel, columns and options, the
# instruction's name, the parent's bundles, the bundles allowed: PERF.md,
# PR 37)
@pytest.mark.parametrize("which,F,kw,name,parents,limit", [
    ("root", 32, {}, "lgbm.histogram.cap", 12_870, 8_500),
    ("split_step", 100, {"tiles": 1}, "lgbm.split_step.dyn", 21_547,
     19_000)])
def test_the_onehot_body_is_scheduled_under_the_parents_bundles(
        topo, llo_dir, which, F, kw, name, parents, limit):
    """libtpu's final schedule of the two kernels that hold the one-hot
    body: the root kernel's step of 32 columns x 2,048 rows read 12,870
    bundles with a ``[256, lanes]`` one-hot and reads 7,618 with 128 rows
    and the planes on the stat rows, the split step at 100 columns (at
    one parent tile a grid step: four add three tiles' compaction beside
    the one body) 21,547 and 17,719 (on the chip the body went from 0.169
    to 0.086 ns a cell: PERF.md, PR 37).  A body that builds the tall
    one-hot again, or casts the stat rows a feature, shows here at no
    chip time."""
    kernel_bundles.read(llo_dir)  # what the compiles before this wrote
    kernel_bundles.KERNELS[which](_shape(topo), F, **kw).compile()
    found = kernel_bundles.read(llo_dir)
    calls = [n for key, n in found.items() if key.startswith(name)]
    assert len(calls) == 1 and parents // 3 < calls[0] < limit, found


# istella-s-220.train's buckets, (queries, Q): one launch each, the last
# of them takes every bucket's sums back to rows
ISTELLA_BUCKETS = ((189, 16), (1591, 32), (5163, 64), (7109, 128),
                   (4164, 256), (929, 512), (100, 1024))


@pytest.mark.parametrize("queries,Q", [(7109, 128), (100, 1024)])
def test_the_pair_gradient_program_compiles_for_v5e_with_its_pairs_fused(
        topo, queries, Q):
    """``jit__lambdarank_grads`` at ``istella-s-220.train``'s size, for its
    fullest bucket and its longest (the tree's last launch): a chunk's
    ``[C, Q, Q]`` pair tensors are 64 MB each in float32 and a dozen of
    them are written down in objectives_rank.py; the chip's compiler fuses
    them into their row sums, and the program's temporaries stay under ONE
    such tensor (15.5 and 0.8 MiB at PR 32).  Unfused, they would be HBM
    traffic of a gigabyte a launch.

    And it reorders by its sorts: an XLA gather of a ``[C, Q]`` chunk by a
    permutation costs the chip 12-30 ns an element (five of them were
    212 of the program's 267 ms a tree: PERF.md, PR 33), a sort operand
    next to nothing.  What is left of single-element movement is the
    transfers between row order and slot order: ONE gather a launch, of
    the scores ``[n + 1]`` into a chunk, and in the last launch TWO more,
    of the buckets' sums ``[row_slots]`` into ``[n]`` rows; no scatter."""
    from lightgbm_tpu import objectives_rank
    from lightgbm_tpu.obs import device_time as dt

    n = 2_043_304
    chunk = max(1, min(queries, (1 << 24) // (Q * Q)))
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    last = (queries, Q) == ISTELLA_BUCKETS[-1]
    ends = {}
    if last:
        ends = {"before": tuple((shape((q * w,)),) * 2
                                for q, w in ISTELLA_BUCKETS[:-1]),
                "row_slot": shape((n,), jnp.int32)}
    compiled = objectives_rank._lambdarank_grads.lower(
        shape((n,)), shape((queries, Q), jnp.int32),
        shape((queries,), jnp.int32), shape((queries, Q), jnp.int32),
        shape((queries, Q)), shape((queries,)), shape((Q,)), shape(()),
        chunk=chunk, **ends).compile()
    assert chunk * Q * Q * 4 == 64 << 20
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
    module = compiled.runtime_executable().hlo_modules()[0]
    prog = dt.program_of_module(module.as_serialized_hlo_module_proto())
    ops = {op: [ins for ins in prog.instrs.values() if ins.opcode == op]
           for op in ("gather", "scatter", "sort")}
    row_slots = sum(q * w for q, w in ISTELLA_BUCKETS)
    assert row_slots == 2_938_352  # the cell's ``rank.row_slots``
    to_rows = (f"f32[{row_slots}]", f"f32[{n}]")
    assert sorted((prog.instrs[g.operands[0]].shape, g.shape)
                  for g in ops["gather"]) == sorted(
        [(f"f32[{n + 1}]", f"f32[{chunk},{Q}]")] + [to_rows] * 2 * last)
    assert not ops["scatter"]
    # out: key, slot, labels, gains; back: slot, the two sums
    assert sorted(len(s.operands) for s in ops["sort"]) == [3, 4]
