"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode runs a kernel body op by op on the CPU and accepts
shapes, layouts and primitives the TPU compiler refuses.  These tests
hand each kernel to the real compiler at the sizes the system runs
(n = 1M points, d = 256, a batch of 64 queries, k = 10), so a kernel
that would not build on the chip fails here, with no chip.  Nothing
runs: a pass says the program compiles and fits, not what it computes.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU compiler library, and test workers
import every test module.

The same lowered programs also show what each matmul asks of the MXU.
Interpret mode on the CPU always multiplies in float32, so only the
lowered program shows whether the chip will: a float32 matmul at the
default precision takes one bf16 pass there, which rounds ids past 256
and distance cross terms.
"""
import base64
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

N, D, B, K, M = 1_000_000, 256, 64, 10, 15
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    saved_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back without one
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved_cache)
    cc.reset_cache()
    if saved_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _shapes(specs, sharding):
    shapes = []
    for spec in specs:
        shape, dtype = (spec if isinstance(spec[0], tuple)
                        else (spec, jnp.float32))
        shapes.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return shapes


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one chip"
    return compiled


def _cases():
    from repro.core.estimator import solve_parameters
    from repro.core.flat_index import candidate_budget
    from repro.kernels.pair_join import _pair_join_jit
    from repro.kernels.pairwise_dist import pairwise_sq_dist_pallas
    from repro.kernels.select import radius_select_pallas
    from repro.kernels.topk import topk_smallest_pallas
    from repro.kernels.verify import verify_topk_pallas

    T = candidate_budget(solve_parameters(1.5, m=M), N, K)
    T_pad = T + max(256, T // 8)
    return {
        "pairwise_estimate": (
            lambda q, x: pairwise_sq_dist_pallas(q, x),
            [(B, M), (N, M)]),
        "pairwise_exact": (
            lambda q, x: pairwise_sq_dist_pallas(q, x),
            [(B, D), (N, D)]),
        "select": (
            lambda d, tau0: radius_select_pallas(d, tau0, T, T_pad=T_pad),
            [(B, N), (B,)]),
        "verify": (
            lambda data, q, cand: verify_topk_pallas(data, q, cand, K),
            [(N, D), (B, D), ((B, T), jnp.int32)]),
        "topk": (
            lambda d: topk_smallest_pallas(d, K),
            [(B, N)]),
        "pair_join": (
            lambda x, key: _pair_join_jit(x, key, K, thresh2=16.0,
                                          block_n=128, interpret=False),
            [(N, D), (N,)]),
    }


@pytest.mark.parametrize("name", ["pairwise_estimate", "pairwise_exact",
                                  "select", "verify", "topk", "pair_join"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _cases()[name]
    _compile(fn, *_shapes(specs, one_chip))


def _ops(lowered):
    """Every operation of a lowered program, nested regions included."""
    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    yield o.operation
                    yield from walk(o.operation)

    return list(walk(lowered.compiler_ir("stablehlo").operation))


def _mosaic_matmul_precisions(lowered):
    """The ``precision`` of every matmul in every Pallas kernel of a
    lowered program, read from the Mosaic module each custom call
    carries ("fp32" = float32 contraction, None = the bf16 default)."""
    from jaxlib.mlir import ir

    found = []
    for op in _ops(lowered):
        if (op.name != "stablehlo.custom_call"
                or ir.StringAttr(op.attributes["call_target_name"]).value
                != "tpu_custom_call"):
            continue
        config = json.loads(ir.StringAttr(op.attributes["backend_config"]).value)
        body = base64.b64decode(config["custom_call_config"]["body"])
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            text = str(ir.Module.parse(body))
        for line in text.splitlines():
            if "tpu.matmul" in line:
                tag = "contract_precision<"
                at = line.find(tag)
                found.append(None if at < 0 else
                             line[at + len(tag):line.index(">", at)])
    return found


def _xla_dot_precisions(lowered):
    """The operand precisions of every XLA dot in a lowered program."""
    return [str(op.attributes["precision_config"])
            if "precision_config" in op.attributes else "DEFAULT"
            for op in _ops(lowered)
            if op.name in ("stablehlo.dot_general", "stablehlo.dot")]


@pytest.mark.parametrize("name,has_matmul", [
    ("pairwise_estimate", True), ("pairwise_exact", True),
    ("select", True),  # the survivor ranks (tile_ranks)
    ("verify", False),  # direct differences, no matmul
    ("topk", False), ("pair_join", True)])
def test_kernel_matmuls_contract_in_float32(one_chip, name, has_matmul):
    fn, specs = _cases()[name]
    lowered = jax.jit(fn).lower(*_shapes(specs, one_chip))
    precisions = _mosaic_matmul_precisions(lowered)
    assert bool(precisions) == has_matmul, precisions
    assert all(p == "fp32" for p in precisions), precisions


@pytest.mark.parametrize("force", ["pallas", "ref"])
def test_search_program_dots_run_at_highest(one_chip, force):
    """The fused search program at n = 1M: the query projection
    (``hashing``), and on the reference path every jnp distance, ask
    XLA for HIGHEST; the kernels ask Mosaic for fp32."""
    from repro.core.estimator import solve_parameters
    from repro.core.flat_index import FlatIndex, candidate_budget
    from repro.core.fused import fused_ann_query
    from repro.core.hashing import ProjectionFamily

    params = solve_parameters(1.5, m=M)
    T = candidate_budget(params, N, K)

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    index = FlatIndex(data=s((N, D)), projected=s((N, M)),
                      family=ProjectionFamily(a=s((D, M))), params=params)
    lowered = jax.jit(
        lambda idx, q: fused_ann_query(idx, q, k=K, T=T, force=force)
    ).lower(index, s((B, D)))
    dots = _xla_dot_precisions(lowered)
    assert dots, "no XLA dot: the query projection is missing"
    assert all(p.count("HIGHEST") == 2 for p in dots), dots
    matmuls = _mosaic_matmul_precisions(lowered)
    assert bool(matmuls) == (force == "pallas")
    assert all(p == "fp32" for p in matmuls), matmuls


def test_verify_row_view_is_one_unpadded_copy(one_chip):
    """The verify kernel copies candidate rows out of an (n, 1, d) view
    of the data, which the compiler lays out one row per tile row: a
    copy of the data per call (the (n, d) array is tiled 8 rows deep),
    not a bitcast, and not padded to 8 rows a point."""
    fn, specs = _cases()["verify"]
    compiled = _compile(fn, *_shapes(specs, one_chip))
    data_bytes = N * D * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert data_bytes <= temp < 1.05 * data_bytes, temp


def test_fused_search_program_compiles_for_v5e(one_chip):
    """The whole jitted estimate → select → verify program at n = 1M,
    with the kernels forced, as a chip's default dispatch runs it."""
    from repro.core.estimator import solve_parameters
    from repro.core.flat_index import FlatIndex, candidate_budget
    from repro.core.fused import fused_ann_query
    from repro.core.hashing import ProjectionFamily

    params = solve_parameters(1.5, m=M)
    T = candidate_budget(params, N, K)

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    index = FlatIndex(data=s((N, D)), projected=s((N, M)),
                      family=ProjectionFamily(a=s((D, M))), params=params)
    compiled = _compile(
        lambda idx, q: fused_ann_query(idx, q, k=K, T=T, force="pallas",
                                       with_count=True),
        index, s((B, D)))
    # estimate, select and verify each run as a kernel
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert np.isfinite(T) and T > K


# -- the profiler's names: stage scopes and kernel names ---------------------

SCOPE_N = 65_536  # enough rows for every stage; quick to compile


def _programs(one_chip):
    """The search, answer and CP programs, compiled for the chip at
    SCOPE_N rows, with the stage scopes each must carry."""
    from repro.core.estimator import solve_parameters
    from repro.core.flat_index import (FlatIndex, ann_query,
                                       answer_distances, candidate_budget)
    from repro.core.hashing import ProjectionFamily
    from repro.kernels.pair_join import _pair_join_jit

    params = solve_parameters(1.5, m=M)
    T = candidate_budget(params, SCOPE_N, K)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    index = FlatIndex(data=s((SCOPE_N, D)), projected=s((SCOPE_N, M)),
                      family=ProjectionFamily(a=s((D, M))), params=params)
    return {
        "fused": (lambda idx, q: ann_query(
            idx, q, k=K, T=T, fused=True, force="pallas", with_count=True),
            (index, s((B, D))),
            ["estimate", "select", "select/seed", "select/radius",
             "select/compact", "select/finish", "verify", "verify/relayout",
             "verify/kernel"]),
        "unfused": (lambda idx, q: ann_query(
            idx, q, k=K, T=T, fused=False, force="pallas"),
            (index, s((B, D))), ["estimate", "select", "verify"]),
        "answer": (answer_distances,
                   (s((SCOPE_N, D)), s((B, K), jnp.int32), s((B, D))),
                   ["answer"]),
        "cp": (lambda x, key: _pair_join_jit(x, key, K, thresh2=16.0,
                                             block_n=128, interpret=False),
               (s((SCOPE_N, D)), s((SCOPE_N,))), ["cp/join"]),
    }


def _op_scopes(hlo_text):
    """Each op's JAX name stack with the ``jit(...)`` levels left out:
    the named scopes a device trace shows in its ``tf_op``."""
    import re

    return {"/".join(p for p in name.split("/") if not p.startswith("jit("))
            for name in re.findall(r'op_name="([^"]*)"', hlo_text)}


@pytest.mark.parametrize("program", ["fused", "unfused", "answer", "cp"])
def test_programs_carry_stage_scopes(one_chip, program):
    """Every stage of the search and CP programs is a named scope in the
    compiled program's op names, which the profiler's device trace
    reports; the scopes change metadata, not the program."""
    fn, args, scopes = _programs(one_chip)[program]
    text = jax.jit(fn).lower(*args).compile().as_text()
    seen = _op_scopes(text)
    for scope in scopes:
        assert any(f"/{scope}/" in f"/{s}/" for s in seen), (scope, seen)


@pytest.mark.parametrize("program,kernels", [
    ("fused", {"pairwise_sq_dist_pallas", "radius_select_pallas",
               "verify_topk_pallas"}),
    ("cp", {"_pair_join_jit"})])
def test_kernels_keep_their_names(one_chip, program, kernels):
    """The kernels are named by ``pallas_call(name=...)``, not by where
    they sit, so a scope or a refactor cannot rename them in the trace
    (the benchmark's readers match these op names)."""
    import re

    fn, args, _ = _programs(one_chip)[program]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = {m.split(".")[0] for m in re.findall(
        r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}
    assert names == kernels, names
