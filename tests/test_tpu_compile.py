"""Compile the main-path kernels and the executor's resident step for a
described TPU v5e, with no chip attached: Mosaic must accept every kernel
at serving shapes, the compiled programs must call the kernels
(``tpu_custom_call``), and the step at a 1M-row resident corpus must fit
one chip's HBM.

The topology is described inside a module-scoped fixture, never at
import, so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.config import HarmonyConfig
from repro.core import build_ivf
from repro.kernels import ops
from repro.kernels.distance import partial_distance_update
from repro.kernels.distance_int8 import int8_partial_distance_update
from repro.kernels.topk_update import running_topk_update
from repro.serve.executor import ExecutorConfig, SpmdExecutor

# serving shapes: a 128-query tile against a 256-row chunk of 128-d rows
M, N, D = 128, 256, 128
HBM_BYTES = 16 * 2**30           # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache here, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


@pytest.mark.parametrize("kernel", ["fp32", "int8", "topk10", "topk40"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    if kernel == "fp32":
        mem = _compile(
            lambda *a: partial_distance_update(*a, interpret=False),
            S((N, D), f32), S((N,), f32), S((M, D), f32), S((M,), f32),
            S((M, N), f32), S((M,), f32))
    elif kernel == "int8":
        mem = _compile(
            lambda *a: int8_partial_distance_update(*a, interpret=False),
            S((N, D), i8), S((N,), f32), S((M, D), i8), S((M,), f32),
            S((), f32), S((M, N), f32), S((M,), f32))
    else:
        k = int(kernel[4:])
        mem = _compile(
            lambda *a: running_topk_update(*a, k=k, interpret=False),
            S((M, N), f32), S((M, N), i32), S((M, k), f32), S((M, k), i32))
    assert mem.argument_size_in_bytes > 0


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_executor_step_compiles_for_one_v5e_chip(topo, monkeypatch,
                                                 precision):
    """The jitted shard_map step, built by the executor's own code, for
    a 1x1 mesh of a described chip at the sift-1M serving bucket: 1M
    resident rows, 32 queries, 512K gathered candidates, 16 probes."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # Mosaic, not interpret
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1024, D)).astype(np.float32)
    index = build_ivf(x, HarmonyConfig(dim=D, nlist=8, nprobe=4,
                                       kmeans_iters=2))
    ex = SpmdExecutor(index, ExecutorConfig(use_pallas=True,
                                            precision=precision))
    # hand the step the described chip and the serving-size residency
    ex.mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                   ("data", "model"))
    ex.cap_full = cap_full = 1 << 20
    k = 40 if precision == "int8" else 10
    qb, cap, nprobe = 32, 1 << 19, 16
    key = (qb, cap, k, nprobe)
    step = ex._make_step(ex._bucket_cfg(*key), key)

    def S(shape, dt, *spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(ex.mesh, P(*spec)))

    xdt = jnp.int8 if precision == "int8" else jnp.float32
    args = [S((1, cap_full, D), xdt, "data", None, "model"),
            S((1, 1, cap_full), jnp.float32, "model", "data", None),
            S((1, cap_full), jnp.int32, "data", None),
            S((1, cap_full), jnp.int32, "data", None)]
    if precision == "int8":
        args.append(S((1,), jnp.float32, "model"))
    args += [S((1, cap), jnp.int32, "data", None),
             S((qb, D), xdt, None, "model"),
             S((qb, nprobe), jnp.int32, None, None),
             S((qb,), jnp.float32, None)]
    mem = _compile(step, *args)
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes >= cap_full * D * jnp.dtype(xdt).itemsize
    assert used < HBM_BYTES, used
