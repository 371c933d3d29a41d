"""The fused kernels of the served path, compiled for a v5e that is
described and not attached.

Interpret mode (tests/test_pallas.py) cannot see what the chip's
compiler refuses — a slice off the tiling, too much VMEM, an
unsupported shape cast — so these compile the kernels at the widths
the 100,000-document index really has (P=16, D_cap=131072, a 512-row
resident cube), for the chip, at no chip time. Nothing runs.

The topology is described inside a fixture: only one process may load
the TPU library, so nothing here touches it while a module is imported
(every xdist worker imports this file; only the worker that RUNS it
may load the library). Keep every such test in this one file.

Measured here (JAX 0.9.0, libtpu 0.0.34, one worker): see CHANGES.md,
PR 22 — each FD shape variant costs ~105 s on a cold chip.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from open_source_search_engine_tpu.query import pallas_scores

P = 16
D = 131072       # D_cap of the 100,000-document index
VC = 512         # its resident cube rows
B = 4            # the latency-path batch bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip's sharding; the persistent compilation cache is
    off around the compiles (an entry written without a chip cannot be
    read back, and the next compile would warn)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *shapes, **statics):
    """Compile for the described chip the way the served path runs it:
    32-bit (the test session turns x64 on; no entry point does)."""
    t0 = time.perf_counter()
    with jax.enable_x64(False):
        compiled = fn.lower(*shapes, **statics).compile()
    print(f"compiled in {time.perf_counter() - t0:.1f} s")
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("T", [4, 8])
def test_min_scores_fused_compiles(one_chip, T):
    """The F2 scoring kernel (scorer.min_scores on a corpus-wide doc
    axis)."""
    _compile(pallas_scores._min_scores_fused,
             _sds(one_chip, (T, P, D), jnp.uint32),
             _sds(one_chip, (T,), jnp.float32),
             _sds(one_chip, (T,), jnp.bool_), interpret=False)


@pytest.mark.parametrize("tail", [False, True], ids=["notail", "tail"])
def test_fd_scores_fused_compiles_with_no_cube_copy(one_chip, tail):
    """The FD kernel, both variants (pure quarter rows; with the
    [B, T, P, D] posting-tail input): scalar-prefetched DMA out of the
    resident cube in the form it is built and kept, quarter rows
    [Vc·4, P/4, D]. The program takes the cube as it stands: its
    arguments hold the cube's 4 GiB once (not 8) and it holds no
    cube-sized temporary, so the wave fits the chip NEXT TO the rest
    of the resident set (~1.5 GB of columns and dense rows at this
    size)."""
    T = 4
    head = (_sds(one_chip, (B, T * 4), jnp.int32),
            _sds(one_chip, (B, T * 4), jnp.int32),
            _sds(one_chip, (1,), jnp.int32),
            _sds(one_chip, (VC * 4, P // 4, D), jnp.uint32))
    rest = (_sds(one_chip, (1, D), jnp.int32),
            _sds(one_chip, (B, T), jnp.float32),
            _sds(one_chip, (B, T), jnp.float32))
    if tail:
        compiled = _compile(
            pallas_scores._fd_scores_fused, *head,
            _sds(one_chip, (B, T, P, D), jnp.uint32), *rest,
            T=T, P=P, interpret=False)
    else:
        compiled = _compile(pallas_scores.fd_scores_fused_notail,
                            *head, *rest, T=T, P=P, interpret=False)
    mem = compiled.memory_analysis()
    print(f"args {mem.argument_size_in_bytes} temp "
          f"{mem.temp_size_in_bytes} out {mem.output_size_in_bytes}")
    # the cube once, plus the tail input (134 MB at B = 4)
    expected = VC * P * D * 4 + (B * T * P * D * 4 if tail else 0)
    assert mem.temp_size_in_bytes < 1 << 30
    assert abs(mem.argument_size_in_bytes - expected) < 64 << 20


@pytest.mark.parametrize("tail", [False, True], ids=["notail", "tail"])
def test_fd_scores_fused_t8_compiles_in_bounded_time(one_chip, tail):
    """The FD kernel rolls its term pairs into one loop at every ``T``,
    so its compile does not grow with the pairs: at ``T`` 8 (28 pairs)
    each variant compiles in seconds, where one that unrolled them did
    not finish in 23 minutes, and its scratch (the pair loop's VMEM
    planes) fits the chip."""
    T = 8
    head = (_sds(one_chip, (B, T * 4), jnp.int32),
            _sds(one_chip, (B, T * 4), jnp.int32),
            _sds(one_chip, (1,), jnp.int32),
            _sds(one_chip, (VC * 4, P // 4, D), jnp.uint32))
    rest = (_sds(one_chip, (1, D), jnp.int32),
            _sds(one_chip, (B, T), jnp.float32),
            _sds(one_chip, (B, T), jnp.float32))
    t0 = time.perf_counter()
    if tail:
        compiled = _compile(
            pallas_scores.fd_scores_fused_t8, *head,
            _sds(one_chip, (B, T, P, D), jnp.uint32), *rest,
            T=T, P=P, interpret=False)
    else:
        compiled = _compile(pallas_scores.fd_scores_fused_notail_t8,
                            *head, *rest, T=T, P=P, interpret=False)
    assert time.perf_counter() - t0 < 120.0
    mem = compiled.memory_analysis()
    expected = VC * P * D * 4 + (B * T * P * D * 4 if tail else 0)
    assert mem.temp_size_in_bytes < 1 << 30
    assert abs(mem.argument_size_in_bytes - expected) < 64 << 20
