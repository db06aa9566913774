"""Ahead-of-time compiles for a described TPU v5e 2x2 at the real widths.

Nothing runs: the TPU compiler, installed with jax, compiles for chips
that are described and not attached, and refuses what the chip would
refuse (unaligned tiles, too much fast memory, programs that do not fit).
Each test asserts the Pallas kernel is in the compiled program
(``tpu_custom_call``).  The topology is described only inside the fixture:
the TPU library may be loaded by one process at a time, and describing it
while modules are imported would hand the test workers different tests.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.config import get_arch
from repro.configs.paper_convex import IJCNN1_LIKE


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2, compiled for as the chip runs: float32
    defaults (the suite's x64 would put f64 into the kernels, which Mosaic
    refuses) and the persistent compilation cache off (what is compiled
    for an absent chip could not be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "absent"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_x64", "jax_enable_compilation_cache")}
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _has_epoch_kernel(text: str) -> bool:
    """The ``vr_epoch`` kernel (its ``pallas_call`` name) is a custom call
    of the compiled program."""
    return re.search(r"%vr_epoch[.\d]* = .*custom_call_target="
                     r'"tpu_custom_call"', text) is not None


def test_vr_update_flat_compiles(one_chip):
    from repro.kernels.vr_update import kernel as vr_kernel

    fn = functools.partial(vr_kernel.vr_update_flat, eta=0.05, m=8,
                           saga=True, decay=2e-4, interpret=False)
    buf = _sds((vr_kernel.TILE,), jnp.float32, one_chip)
    assert _has_kernel(jax.jit(fn).lower(*[buf] * 5).compile())


def test_fused_centralvr_epoch_compiles_at_ijcnn1(one_chip):
    from repro.core import fused

    n, d = IJCNN1_LIKE.n, IJCNN1_LIKE.d
    fp = (0.05, IJCNN1_LIKE.lam, False, None)

    def epoch(A, b, x, table, gbar, order):
        return fused.centralvr_epoch(A, b, "logistic", x, table, gbar,
                                     order, fp)[:3]

    args = (_sds((n, d), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip),
            _sds((d,), jnp.float32, one_chip),
            _sds((n,), jnp.float32, one_chip),
            _sds((d,), jnp.float32, one_chip),
            _sds((n,), jnp.int32, one_chip))
    assert _has_kernel(jax.jit(epoch).lower(*args).compile())


@pytest.mark.parametrize("p,ns,d,kind", [
    (4, 8_750, 22, "logistic"),      # ijcnn1.sync-p4: a worker's shard
    (1, 35_000, 22, "logistic"),     # ijcnn1.centralvr-p1
    (4, 115_928, 90, "ridge"),       # the MILLIONSONG cells: 167 MB
], ids=["ijcnn1-p4", "ijcnn1-p1", "millionsong-p4"])
@pytest.mark.parametrize("init", [False, True], ids=["vr", "init"])
def test_vr_epoch_kernel_compiles_at_the_cells(one_chip, p, ns, d, kind,
                                               init):
    """The local-epoch kernel with its visit-order gather and scatter, at
    the shards the cells run; MILLIONSONG's does not fit in VMEM, so a
    block that overflows it shows here."""
    from repro.kernels.vr_epoch import ops

    def epoch(A, b, perm, x, eta, lam, table, gbar):
        if init:
            return ops.local_epochs(A, b, perm, x, eta, lam, kind=kind,
                                    interpret=False)
        return ops.local_epochs(A, b, perm, x, eta, lam, kind=kind,
                                table=table, gbar=gbar, interpret=False)

    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    compiled = jax.jit(epoch).lower(
        f32((p, ns, d)), f32((p, ns)), _sds((p, ns), jnp.int32, one_chip),
        f32((d,)), f32(()), f32(()), f32((p, ns)), f32((d,))).compile()
    assert _has_epoch_kernel(compiled.as_text())


def test_rmsnorm_compiles_at_qwen2_7b(one_chip):
    from repro.kernels.rmsnorm import ops as rn_ops

    d = get_arch("qwen2-7b").d_model
    compiled = rn_ops.rmsnorm.lower(
        _sds((4096, d), jnp.bfloat16, one_chip),
        _sds((d,), jnp.float32, one_chip), interpret=False).compile()
    assert _has_kernel(compiled)


def test_flash_attention_compiles_at_qwen2_7b(one_chip):
    from repro.kernels.flash_attention import ops as fa_ops

    cfg = get_arch("qwen2-7b")
    S, hd = 4096, cfg.head_dim
    compiled = fa_ops.flash_attention.lower(
        _sds((1, S, cfg.num_heads, hd), jnp.bfloat16, one_chip),
        _sds((1, S, cfg.num_kv_heads, hd), jnp.bfloat16, one_chip),
        _sds((1, S, cfg.num_kv_heads, hd), jnp.bfloat16, one_chip),
        interpret=False).compile()
    assert _has_kernel(compiled)


def test_spmd_sync_runner_compiles_on_four_chips(topo, monkeypatch):
    from repro import kernels
    from repro.core import spmd

    p, rounds = 4, 5
    ns, d = IJCNN1_LIKE.n // p, IJCNN1_LIKE.d
    mesh = Mesh(np.asarray(topo.devices[:p]), (spmd.WORKER_AXIS,))
    workers = NamedSharding(mesh, P(spmd.WORKER_AXIS))
    per_round = NamedSharding(mesh, P(None, spmd.WORKER_AXIS))
    rep = NamedSharding(mesh, P())
    # the static fused tuple is passed directly: fused.make_params would
    # see this process's CPU backend and pick interpret mode
    runner = spmd._sync_runner(mesh, "logistic",
                               (0.05, IJCNN1_LIKE.lam, False, None))
    compiled = runner.lower(
        _sds((p, ns, d), jnp.float32, workers),
        _sds((p, ns), jnp.float32, workers),
        _sds((), jnp.float32, rep), _sds((), jnp.float32, rep),
        _sds((), jnp.float32, rep),
        _sds((p, ns), jnp.int32, workers),
        _sds((rounds, p, ns), jnp.int32, per_round)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    # unfused, each chip's epochs are the vr_epoch kernel; the chooser
    # reads the platform, which is this process's CPU, so it is told
    monkeypatch.setattr(kernels, "has_pallas_support", lambda: True)
    runner = spmd._sync_runner(mesh, "logistic", None, None)
    text = runner.lower(
        _sds((p, ns, d), jnp.float32, workers),
        _sds((p, ns), jnp.float32, workers),
        _sds((), jnp.float32, rep), _sds((), jnp.float32, rep),
        _sds((), jnp.float32, rep),
        _sds((p, ns), jnp.int32, workers),
        _sds((rounds, p, ns), jnp.int32, per_round)).compile().as_text()
    assert _has_epoch_kernel(text)
    assert "all-reduce" in text
