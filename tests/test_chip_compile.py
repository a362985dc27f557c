"""Compile the device path for a described TPU v5e chip.

Nothing here runs on a chip: each test lowers and compiles a kernel or
jitted program of the ExtendBlock -> DAH path for a v5e that is
described, not attached, so what the chip's compiler (Mosaic for the
Pallas kernels) refuses fails here at no chip time. Interpret-mode and
CPU tests cannot see these refusals: unaligned block shapes and
kernels that capture array constants both passed them.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the test
workers all import this file. Every compile of this kind lives in this
one file, so one worker holds the library.
"""

import os

import pytest

SHARE = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    import jax

    compiled = jax.jit(fn).lower(*specs).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_encode2d_k128(one_chip):
    import jax.numpy as jnp

    from celestia_tpu.ops import rs_pallas

    k = 128
    x = _spec((k, k * SHARE), jnp.uint8, one_chip)
    m2 = _spec((8 * k, 8 * k), jnp.int8, one_chip)
    c = _compile(rs_pallas.encode2d, x, m2)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("k", [16, 32, 64, 128])
def test_fused_extend_hash_kernel(one_chip, k):
    """The kernel the TPU default (extend_tpu._fused_active) runs for
    every k that rs_pallas.fused_supported admits."""
    import jax.numpy as jnp

    from celestia_tpu.ops import rs_pallas

    assert rs_pallas.fused_supported(k, k * SHARE)
    x = _spec((k, k * SHARE), jnp.uint8, one_chip)
    m2 = _spec((8 * k, 8 * k), jnp.int8, one_chip)
    c = _compile(rs_pallas.encode2d_hash, x, m2)
    assert "tpu_custom_call" in c.as_text()


def test_leaf_digests2d_k128(one_chip):
    import jax.numpy as jnp

    from celestia_tpu.ops import rs_pallas

    k = 128
    x = _spec((k, k * SHARE), jnp.uint8, one_chip)
    ns = _spec((k, k, rs_pallas.NS_PAD), jnp.uint8, one_chip)
    c = _compile(rs_pallas.leaf_digests2d, x, ns)
    assert "tpu_custom_call" in c.as_text()


def test_sha256_words_65536_lanes(one_chip):
    """The k=128 leaf set: 65,536 NMT leaf messages of 9 blocks."""
    import jax.numpy as jnp

    from celestia_tpu.ops import sha256_pallas

    words = _spec((144, 65536), jnp.uint32, one_chip)
    c = _compile(lambda w: sha256_pallas.sha256_words(w), words)
    assert "tpu_custom_call" in c.as_text()


def test_extend_and_roots_only_default_spelling(one_chip, monkeypatch):
    """The jitted roots program as the chip would trace it: the fused
    decision is taken for a TPU backend, which the test (not the
    program) stands in for."""
    import jax
    import jax.numpy as jnp

    from celestia_tpu.ops import extend_tpu

    k = 16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert extend_tpu._fused_active(k)
    shares = _spec((k, k, SHARE), jnp.uint8, one_chip)
    m2 = _spec((8 * k, 8 * k), jnp.uint8, one_chip)
    c = _compile(extend_tpu.extend_and_roots_only, shares, m2)
    assert c.as_text().count("tpu_custom_call") == 4  # leaf + 3 fused


def test_gspmd_batched_spelling_carries_no_kernel(topo, monkeypatch):
    """XLA refuses to partition a Mosaic kernel ("cannot be
    automatically partitioned"), so the sharding-annotated batched
    program must lower without one even where the TPU default would
    pick the fused spelling. Lowering only: the full four-chip compile
    (~1 min) is chip_smoke.py --four-chips' job."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from celestia_tpu import parallel
    from celestia_tpu.ops import extend_tpu

    k = 16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert extend_tpu._fused_active(k)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("dp", "sp"))
    x = jax.ShapeDtypeStruct((4, k, k, SHARE), jnp.uint8,
                             sharding=NamedSharding(mesh, P("dp", "sp", None, None)))
    text = parallel.sharded_extend_and_root(mesh, k).lower(x).as_text()
    assert "tpu_custom_call" not in text


def test_kernel_body_carries_no_checkout_path(one_chip):
    """The Mosaic body of a Pallas kernel keeps its source locations and
    is part of the persistent-cache key: with an absolute path in it, a
    kernel compiled in one checkout misses the cache in every other.
    enable_compile_cache() makes the paths relative to the checkout."""
    import base64
    import json
    import re

    import jax
    import jax.numpy as jnp

    from celestia_tpu.ops import enable_compile_cache, sha256_pallas

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_hlo_source_file_canonicalization_regex)
    try:
        enable_compile_cache()
        words = _spec((144, 1024), jnp.uint32, one_chip)
        text = jax.jit(sha256_pallas.sha256_words).lower(words).as_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          saved[1])
    config = re.search(r'backend_config = "(.*?)"', text).group(1)
    body = base64.b64decode(json.loads(config.replace("\\22", '"'))
                            ["custom_call_config"]["body"])
    assert b"celestia_tpu/ops/sha256_pallas.py" in body
    assert root.encode() not in body
