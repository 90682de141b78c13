"""The reference's layer program compiled for a described TPU v5e (no chip
attached) at the configurations' real widths and the largest sample a run
can draw: it must compile and fit one chip's memory."""

import json
import os
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HBM = 16e9


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("config,batch", [("smollm-360m", 16),
                                          ("granite-8b", 8)])
def test_reference_layer_fits_one_v5e(chip, config, batch):
    import jax
    import jax.numpy as jnp
    from bench.reference import model as ref
    arch = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())["arch"]
    arch_t = tuple(sorted((k, v) for k, v in arch.items()
                          if isinstance(v, (int, float, str, bool))))
    d, L, nq, nkv, hd, ff, V = ref.dims(arch)
    x = jax.ShapeDtypeStruct((batch, 2048, d), jnp.float32, sharding=chip)
    shapes = {name: shape[1:] for name, shape, _, _ in ref.leaf_specs(arch)
              if len(shape) >= 2 and shape[0] == L}
    w = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
         for k, s in shapes.items()}
    with jax.default_matmul_precision("highest"):
        compiled = ref.layer.lower(x, w, arch_t, "f32").compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 0.75 * HBM, used
