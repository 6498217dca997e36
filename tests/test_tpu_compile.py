"""The GMM kernels and the attention kernel compile for a TPU v5e that is
described, not attached.

Interpret mode (tests/test_kernels.py) checks the kernels' math; only the
chip's own compiler (Mosaic) checks that it accepts their layouts, and its
refusals are what kept the detection plane off the chip. These compiles need
the TPU compiler, not a chip: the topology is described inside a fixture,
which skips where it cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gmm_score import gmm_best_pallas, gmm_score_pallas
from repro.kernels.gmm_stats import gmm_stats_pallas, gmm_update_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _score(spec, bn):
    return gmm_score_pallas.lower(spec("X"), spec("means"), spec("U"),
                                  block_n=bn)


def _best(spec, bn):
    return gmm_best_pallas.lower(spec("X"), spec("means"), spec("U"),
                                 block_n=bn)


def _stats(spec, bn):
    return gmm_stats_pallas.lower(spec("X"), spec("logw"), spec("means"),
                                  spec("U"), nvalid=spec("nvalid"),
                                  block_n=bn)


def _update(spec, bn):
    return gmm_update_pallas.lower(spec("X"), spec("logw"), spec("means"),
                                   spec("U"), nvalid=spec("nvalid"),
                                   block_n=bn)


def _shapes(one_chip, N, D, K):
    shapes = {"X": ((N, D), jnp.float32), "means": ((K, D), jnp.float32),
              "U": ((K, D, D), jnp.float32), "logw": ((K,), jnp.float32),
              "nvalid": ((), jnp.int32)}

    def spec(name):
        shape, dtype = shapes[name]
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return spec


# (rows, D, K, block_n): detection-plane buckets (repro.detect.cache) at the
# features' widths, up to the largest bucket at the streaming EM's block
SHAPES = [(256, 3, 3, 256), (4096, 4, 3, 1024), (65536, 4, 5, 4096)]


@pytest.mark.parametrize("N,D,K,block_n", SHAPES)
@pytest.mark.parametrize("lower", [_score, _best, _stats, _update],
                         ids=["score", "best", "stats", "update"])
def test_gmm_kernel_compiles_for_v5e(one_chip, lower, N, D, K, block_n):
    compiled = lower(_shapes(one_chip, N, D, K), block_n).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lower,name", [
    (_score, "gmm_score"), (_best, "gmm_best"), (_stats, "gmm_stats"),
    (_update, "gmm_update")], ids=["score", "best", "stats", "update"])
def test_gmm_kernel_carries_its_name(one_chip, lower, name):
    """Each kernel's custom call is named after its ``pallas_call``
    ``name=``, which is what the device trace's reduction matches
    (``benchmarks/onchip/program_trace.py``): not after the jitted
    wrapper that holds it."""
    text = lower(_shapes(one_chip, 256, 3, 3), 256).compile().as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(c.startswith(f"%{name}.") for c in calls), calls


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_attention_kernel_compiles_for_v5e(one_chip, grad):
    """The train step's attention at GPT-2's shape (bf16 [8, 12, 1024, 64],
    causal) compiles to the ``splash_mha_*`` kernels: the forward, and for the
    gradient the forward with its residuals and one fused dq/dk/dv kernel."""
    from repro.models.attention import _splash

    def attend(q, k, v):
        return _splash(q, k, v, True, 0.125)

    if grad:
        attend = jax.grad(
            lambda *a, f=attend: jnp.sum(f(*a).astype(jnp.float32)),
            argnums=(0, 1, 2))
    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(attend).lower(x, x, x).compile().as_text()
    calls = {line.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    want = {"splash_mha_fwd_no_residuals"}
    if grad:
        want = {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}
    assert calls == want, calls
