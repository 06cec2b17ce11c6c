"""Process runtime helpers: the persistent compile cache's directory,
compile accounting, the device report, and the kernel paths a run
names in its report."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.kernels.common import kernel_paths
from repro.launch import runtime


def test_compile_cache_keeps_a_directory_the_environment_names(
        monkeypatch, tmp_path):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.use_compile_cache() == str(tmp_path)
    assert os.environ[runtime.CACHE_ENV] == str(tmp_path)


def test_compile_cache_defaults_to_a_fixed_directory_in_the_checkout(
        monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    path = runtime.use_compile_cache()
    # children inherit it through the environment
    assert os.environ[runtime.CACHE_ENV] == path
    assert path == str(runtime.DEFAULT_CACHE_DIR)
    assert runtime.DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (runtime.DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
    monkeypatch.delenv(runtime.CACHE_ENV)
    assert runtime.use_compile_cache() == path      # the same every time


def test_compile_stats_counts_compiles_inside_the_block_only():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    with runtime.compile_stats() as stats:
        f(jnp.ones((7, 5))).block_until_ready()
    assert stats["compile_s"] > 0
    assert stats["cache_hits"] == 0
    seen = dict(stats)
    jax.jit(lambda x: x - 2.0)(jnp.ones(3)).block_until_ready()
    assert stats == seen                 # listeners gone after the block


def test_device_report_names_the_device():
    rep = runtime.device_report()
    dev = jax.devices()[0]
    assert (rep["platform"], rep["kind"], rep["count"]) == (
        dev.platform, dev.device_kind, len(jax.devices()))
    assert rep["local_ids"] == [d.id for d in jax.local_devices()]


@pytest.mark.parametrize("arch,want", [
    ("stablelm-1.6b", {"attention"}),
    ("mamba2-2.7b", {"mixer"}),
])
def test_kernel_paths_follow_backend_and_mesh(arch, want):
    cfg = dataclasses.replace(get_reduced(arch), attention_backend="pallas",
                              mixer_backend="pallas")
    assert set(kernel_paths(cfg)) == want
    assert set(kernel_paths(cfg).values()) == {"pallas"}
    # pallas_call has no partitioning rule: a mesh takes the jnp lowering
    assert set(kernel_paths(cfg, mesh_devices=4).values()) == {"jnp"}
    auto = dataclasses.replace(cfg, attention_backend="auto",
                               mixer_backend="auto")
    assert set(kernel_paths(auto).values()) == {"jnp"}      # on the CPU
