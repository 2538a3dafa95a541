"""Sharding-rule engine: divisibility fallbacks + real-config specs.

These run on 1 device by constructing abstract meshes (Mesh over a numpy
array of the single CPU device is not possible for 256 entries, so we
use jax.sharding.AbstractMesh, which PartitionSpec validation accepts).
"""

import hypothesis.strategies as st
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_names, get_arch
from repro.distribution.sharding import (
    batch_spec, cache_shardings, make_spec, param_shardings)
from repro.launch import steps


def mesh16x16():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh2x16x16():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _spec_divides(spec, shape, mesh) -> bool:
    for dim, axes in zip(shape, tuple(spec)):
        if axes is None:
            continue
        axes = axes if isinstance(axes, tuple) else (axes,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % n:
            return False
    return True


def test_make_spec_falls_back_on_indivisible():
    mesh = mesh16x16()
    # dim 8 can't shard over 16-way model => replicated
    spec = make_spec([[("model",)]], (8,), mesh)
    assert tuple(spec) == (None,)
    spec = make_spec([[("model",)]], (32,), mesh)
    assert tuple(spec) == ("model",)


def test_make_spec_priority_order():
    mesh = mesh2x16x16()
    # prefer (pod,data) jointly; batch 8 only divides by pod(2) -> falls
    # through to data? 8 % (2*16)=8 !=0; [("pod","data")] then [("data",)]
    spec = make_spec([[("pod", "data"), ("data",)]], (8,), mesh)
    assert tuple(spec) == (None,)          # 8 % 16 != 0 too
    spec = make_spec([[("pod", "data"), ("data",)]], (16,), mesh)
    assert tuple(spec) == ("data",)
    spec = make_spec([[("pod", "data"), ("data",)]], (64,), mesh)
    assert tuple(spec) == (("pod", "data"),)


def test_no_axis_used_twice():
    mesh = mesh16x16()
    spec = make_spec([[("model",)], [("model",), ("data",)]],
                     (32, 32), mesh)
    assert tuple(spec) == ("model", "data")


@pytest.mark.parametrize("name", all_arch_names())
@pytest.mark.parametrize("mk", [mesh16x16, mesh2x16x16])
def test_param_shardings_valid_for_all_archs(name, mk):
    """Every sharded dim divides its axis product, for the FULL configs
    on both production meshes."""
    cfg = get_arch(name)
    mesh = mk()
    pspecs = steps.param_specs(cfg)
    shardings = param_shardings(pspecs, mesh)
    leaves = jax.tree_util.tree_leaves(pspecs)
    shs = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(leaves) == len(shs)
    n_sharded = 0
    for leaf, sh in zip(leaves, shs):
        assert _spec_divides(sh.spec, leaf.shape, mesh), \
            (leaf.shape, sh.spec)
        if any(a is not None for a in tuple(sh.spec)):
            n_sharded += 1
    # the big tensors must actually shard (not everything replicated)
    assert n_sharded >= len(leaves) // 2


@pytest.mark.parametrize("name", ["jamba-v0.1-52b", "rwkv6-3b",
                                  "nemotron-4-340b"])
def test_cache_shardings_valid(name):
    from repro.configs.base import SHAPES
    cfg = get_arch(name)
    mesh = mesh16x16()
    cspecs = steps.cache_specs(cfg, SHAPES["decode_32k"])
    shardings = cache_shardings(cspecs, mesh)
    leaves = jax.tree_util.tree_leaves(cspecs)
    shs = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    for leaf, sh in zip(leaves, shs):
        assert _spec_divides(sh.spec, leaf.shape, mesh), \
            (leaf.shape, sh.spec)


def test_batch_spec_long_context_batch1():
    mesh = mesh16x16()
    assert tuple(batch_spec(mesh, 1, 1)) == (None, None)
    assert tuple(batch_spec(mesh, 32, 1)) == ("data", None)
    mesh3 = mesh2x16x16()
    assert tuple(batch_spec(mesh3, 256, 1))[0] == ("pod", "data")


@given(st.lists(st.integers(1, 4096), min_size=1, max_size=4))
@settings(max_examples=40, deadline=None)
def test_make_spec_always_valid(shape):
    mesh = mesh2x16x16()
    rule = [[("pod", "data"), ("data",), ("model",)]] * len(shape)
    spec = make_spec(rule, shape, mesh)
    assert _spec_divides(spec, shape, mesh)
