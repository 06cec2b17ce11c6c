"""The Pallas kernels of the main path compile for a TPU v5e chip at the
configurations' real widths.

Nothing runs: the installed TPU compiler compiles for a described
``v5e:2x2`` topology, so the layout and memory rules that interpret mode
never checks are enforced here, on a host with no chip.  The topology is
described inside a fixture (only the worker that runs this file loads
the TPU library), never while a module is imported.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd_scan.ops import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# (H, Kh, hd): stablelm-1.6b is multi-head, granite-3-2b groups 4:1
ATTN_WIDTHS = {"stablelm-1.6b": (32, 32, 64), "granite-3-2b": (32, 8, 64)}


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles_for_v5e(one_chip, arch, grad):
    H, Kh, hd = ATTN_WIDTHS[arch]
    B, S = 2, 1024
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, Kh, hd), jnp.bfloat16,
                              sharding=one_chip)
    attn = partial(flash_attention, causal=True, interpret=False)
    fn = (jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
                   argnums=(0, 1, 2)) if grad else attn)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


def test_ssd_scan_compiles_for_v5e(one_chip):
    # mamba2-2.7b: d_inner 5120 -> 80 heads of 64, d_state 128, chunk 256
    Bs, S, nh, hp, g, N = 2, 1024, 80, 64, 1, 128

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    fn = partial(ssd_scan, chunk=256, interpret=False, return_state=True)
    text = _compiled_text(fn, shape(Bs, S, nh, hp),
                          shape(Bs, S, nh, dtype=jnp.float32),
                          shape(nh, dtype=jnp.float32), shape(Bs, S, g, N),
                          shape(Bs, S, g, N))
    assert "tpu_custom_call" in text
