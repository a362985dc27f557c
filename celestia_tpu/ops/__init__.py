"""TPU compute path: GF(2^8) Reed-Solomon, SHA-256, NMT kernels."""

import os


def _machine_fingerprint() -> str:
    """Short digest of what makes a CPU-compiled executable portable:
    the host's instruction-set features plus the jaxlib version.

    XLA:CPU AOT results embed the COMPILE machine's feature set; loading
    one on a host missing those features SIGILLs/segfaults (observed:
    the shared cache dir was written by a box with amx/avx512 variants
    this host lacks, and a cache READ crashed the test suite). The
    cache's own key does not include host features, so partition the
    directory by them instead."""
    import hashlib
    import platform

    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 says "flags", aarch64 says "Features" — missing
                # either collapses the fingerprint to machine|version
                # and re-shares partitions across ISA-different hosts
                if line.lower().startswith(("flags", "features")):
                    feats = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        feats = platform.processor()
    try:
        import jaxlib

        ver = getattr(jaxlib, "__version__", "?")
    except Exception:  # noqa: BLE001
        ver = "?"
    return hashlib.sha256(
        f"{platform.machine()}|{ver}|{feats}".encode()
    ).hexdigest()[:16]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache (idempotent) and
    return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the directory, and
    nothing here configures another. Otherwise the cache lives at a
    fixed path inside the checkout, `.jax_cache/<host fingerprint>`,
    partitioned by _machine_fingerprint so one box's AOT executables
    never load on a box with different CPU features (a hard crash, not
    a recompile). The repair sweep and the k=128 extend cost tens of
    seconds to compile cold; a warm cache turns every later process
    start into a disk load.

    The Pallas kernels' serialized Mosaic body keeps its source
    locations, and that body is part of the cache key, so a kernel
    compiled in one checkout missed the cache in another. Source paths
    are therefore made relative to the checkout root."""
    import re

    import jax

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache", _machine_fingerprint())
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(root + os.sep))
    # 3 s threshold: only the expensive programs (device-path k=128
    # extends, repair sweeps, sharded steps) are worth persisting
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3.0)
    return cache_dir
