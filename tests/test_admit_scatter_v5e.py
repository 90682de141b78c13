"""Admission's cache scatter compiled for a described (not attached) TPU
v5e: at smollm-360m's real slot cache it writes the admitted rows into the
donated cache in place, and on granite-8b's four-chip mesh it moves nothing
between chips. The topology is described inside a fixture (never at import:
only one process at a time may load the TPU library), and every test skips
where it cannot be described."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed.sharding import (ShardingContext, params_shardings,
                                        serve_rules, sharding_context)
from repro.models.api import build_model
from repro.serving.engine import admit_scatter

SLOTS, MAX_LEN, NB = 32, 2048, 2
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _bytes_accessed(compiled) -> float:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return cost["bytes accessed"]


def test_smollm_scatter_writes_rows_in_place(topo):
    """Two admitted rows into smollm-360m's [32, 32, 2048, 5, 64] bf16 slot
    cache: the output is the donated cache, with no cache-sized temporary,
    and the program touches the rows, not the 2.7 GB cache (the masked
    gather-select it replaces took 6.55 GB of temporaries and 21.7 GB)."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P())
    L, hkv, d = 32, 5, 64
    kv = (L, SLOTS, MAX_LEN, hkv, d)
    cache = {"k": jax.ShapeDtypeStruct(kv, jnp.bfloat16, sharding=sh),
             "v": jax.ShapeDtypeStruct(kv, jnp.bfloat16, sharding=sh),
             "lengths": jax.ShapeDtypeStruct((SLOTS,), jnp.int32,
                                             sharding=sh)}
    pkv = (L, NB, MAX_LEN, hkv, d)
    pcache = {"k": jax.ShapeDtypeStruct(pkv, jnp.bfloat16, sharding=sh),
              "v": jax.ShapeDtypeStruct(pkv, jnp.bfloat16, sharding=sh),
              "lengths": jax.ShapeDtypeStruct((NB,), jnp.int32, sharding=sh)}
    compiled = jax.jit(admit_scatter, donate_argnums=(0,)).lower(
        cache, pcache, jax.ShapeDtypeStruct((NB,), jnp.int32, sharding=sh),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= 2 * math.prod(kv) * 2, mem
    assert _bytes_accessed(compiled) < 1e9, _bytes_accessed(compiled)


def test_granite_tp4_scatter_moves_nothing_between_chips(topo):
    """granite-8b's widths (two layers deep) on the [1, 4] mesh, its slot
    cache sharded over kv heads and the prefilled rows in the sharding the
    prefill returns them in: the scatter holds no collective, no copy or
    transpose of a row-sized array, and returns the cache as it was
    sharded."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_config("granite-8b"), num_layers=2)
    rules = serve_rules(False)
    ctx = ShardingContext(mesh, rules)
    model = build_model(cfg, mesh, rules)
    params = _sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                  params_shardings(model.param_axes, ctx))
    rep = NamedSharding(mesh, P())

    def prefill(params, tokens, lengths):
        with sharding_context(mesh, rules):
            return model.prefill(params, {"tokens": tokens,
                                          "lengths": lengths},
                                 max_len=MAX_LEN)

    pre = jax.jit(prefill).lower(
        params, jax.ShapeDtypeStruct((NB, 128), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((NB,), jnp.int32, sharding=rep)).compile()
    pcache = _sds(jax.eval_shape(lambda: model.init_cache(NB, MAX_LEN)),
                  pre.output_shardings[1])
    cache_sh = params_shardings(model.cache_axes(SLOTS, MAX_LEN), ctx)
    cache = _sds(jax.eval_shape(lambda: model.init_cache(SLOTS, MAX_LEN)),
                 cache_sh)
    compiled = jax.jit(admit_scatter, donate_argnums=(0,)).lower(
        cache, pcache, jax.ShapeDtypeStruct((NB,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    hlo = compiled.as_text()
    assert [c for c in COLLECTIVES if c in hlo] == []
    row = (cfg.num_layers * MAX_LEN * (cfg.num_kv_heads // 4)
           * cfg.resolved_head_dim)
    big = [line.strip()[:160] for line in hlo.splitlines()
           if (m := re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(",
                              line))
           and math.prod(int(x) for x in m.group(1).split(",")) >= row]
    assert big == []
    for name in ("k", "v"):
        assert compiled.output_shardings[name].is_equivalent_to(
            cache_sh[name], 5)
