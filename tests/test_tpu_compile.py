"""Compile-only tests of the four scheduler kernels for a TPU v5e.

Nothing runs on a chip.  Each test lowers one fused kernel at the server
count, queue capacity and job-slot widths of ``chip_smoke.py``'s sweep
(L=1000, K=16, Qcap=4096, window 40; the multi-resource kernel at the
``google2011-fleet-1000`` benchmark configuration's K=20, A_max=64 and
two-resource per-server capacity plane; the VQS-BF kernel also at
L=4000, past the ~1100 servers its VMEM admitted while it kept per-server
state in ``(L, 1)`` columns) and compiles it for a v5e described by
``jax.experimental.topologies``, so a block shape the TPU tiling rule
refuses, a primitive the kernel compiler cannot lower, or a VMEM estimate
short of what the compiler allocates fails here and not on the chip.

The VMEM checks pin each kernel's scoped-VMEM limit to its
``*_vmem_bytes`` estimate: the compile must pass (the estimate covers the
allocation) and must fail at 3/4 of it (the estimate is tight).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.core.engine.vqs import _default_drain
from repro.kernels.bfjs import bfjs as bfjs_mod
from repro.kernels.bfjs_mr import bfjs_mr as bfjs_mr_mod
from repro.kernels.common import SCOPED_VMEM_BYTES, ensemble_plane_bytes
from repro.kernels.vqs import vqs as vqs_mod
from repro.kernels.vqs_bf import vqs_bf as vqs_bf_mod

G, T, TW = 2, 80, 40
L, K, Qcap, A_max, J, R = 1000, 16, 4096, 16, 4, 2
W = A_max + 4
V5E_HBM_BYTES = 16 * 10 ** 9
# the multi-resource kernel's job slots, arrival lanes and work bound
MR_K, MR_A, MR_W = 20, 64, 96
# servers of the wide VQS-BF case (its servers sit on lanes)
WIDE_L = 4000

# kernel -> (module, call, estimate, size lanes, output planes); the
# multi-resource call also takes the (L, R) capacity plane
KERNELS = {
    "bfjs": (bfjs_mod,
             lambda n, s, d: bfjs_mod.bfjs_pallas(
                 n, s, d, L=L, K=K, Qcap=Qcap, A_max=A_max, work_steps=W,
                 window=TW),
             bfjs_mod.bfjs_vmem_bytes(L, K, Qcap, A_max, TW), 1, 3),
    "vqs": (vqs_mod,
            lambda n, s, d: vqs_mod.vqs_pallas(
                n, s, d, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                work_steps=W, drain=_default_drain(K, J), window=TW),
            vqs_mod.vqs_vmem_bytes(J, L, K, Qcap, A_max, TW), 1, 3),
    "vqs_bf": (vqs_bf_mod,
               lambda n, s, d: vqs_bf_mod.vqs_bf_pallas(
                   n, s, d, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                   work_steps=W, window=TW),
               vqs_bf_mod.vqs_bf_vmem_bytes(J, L, K, Qcap, A_max, TW), 1, 3),
    "vqs_bf_wide": (vqs_bf_mod,
                    lambda n, s, d: vqs_bf_mod.vqs_bf_pallas(
                        n, s, d, J=J, L=WIDE_L, K=K, Qcap=Qcap, A_max=A_max,
                        work_steps=W, window=TW),
                    vqs_bf_mod.vqs_bf_vmem_bytes(J, WIDE_L, K, Qcap, A_max,
                                                 TW), 1, 3),
    "bfjs_mr": (bfjs_mr_mod,
                lambda n, s, d, cap: bfjs_mr_mod.bfjs_mr_pallas(
                    n, s, d, cap, L=L, K=MR_K, Qcap=Qcap, A_max=MR_A,
                    work_steps=MR_W, window=TW),
                bfjs_mr_mod.bfjs_mr_vmem_bytes(L, MR_K, Qcap, MR_A, R, TW),
                R, 2 + R),
}


def _widths(name):
    """(L, K, A_max) of a kernel's compile."""
    if name == "bfjs_mr":
        return L, MR_K, MR_A
    return (WIDE_L if name == "vqs_bf_wide" else L), K, A_max


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(name, one_chip):
    _, call, _, size_lanes, _ = KERNELS[name]
    l, k, a = _widths(name)
    spec = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    sizes = (G, T, a) if size_lanes == 1 else (G, T, a, size_lanes)
    args = [spec((G, T)), spec(sizes, jnp.float32), spec((G, T, l * k + a))]
    if name == "bfjs_mr":
        args.append(spec((L, R)))
    # the kernels' jitted wrappers cache their lowerings; a test that
    # changes the VMEM limit must not reuse another test's
    jax.clear_caches()
    return jax.jit(call).lower(*args).compile()


def _pin_vmem_limit(monkeypatch, name, limit):
    monkeypatch.setattr(KERNELS[name][0], "compiler_params",
                        lambda _: pltpu.CompilerParams(vmem_limit_bytes=limit))


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e_within_estimates(name, one_chip,
                                                  no_compile_cache,
                                                  monkeypatch):
    _, _, estimate, size_lanes, out_lanes = KERNELS[name]
    assert estimate <= SCOPED_VMEM_BYTES  # the default budget admits it
    _pin_vmem_limit(monkeypatch, name, estimate)
    compiled = _compile(name, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    l, k, a = _widths(name)
    planes = ensemble_plane_bytes(G, T, stream_lanes=1 + a * size_lanes
                                  + l * k + a, out_lanes=out_lanes)
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes
    # HBM pads each plane's lane axis to 128; at these widths the
    # unpadded estimate that gates the launch is within 2% of it
    assert planes <= used * 1.02 and used <= planes * 1.02
    assert used + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_vmem_estimate_is_tight(name, one_chip, no_compile_cache,
                                       monkeypatch):
    _pin_vmem_limit(monkeypatch, name, KERNELS[name][2] * 3 // 4)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(name, one_chip)
