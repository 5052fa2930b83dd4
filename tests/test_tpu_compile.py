"""The main-path kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles for a chip that is described, not
attached (jax.experimental.topologies), so the chip's own compiler
refuses here what interpret-mode tests cannot see — SMEM/VMEM overflow,
layouts, compiler crashes. The topology is described inside a fixture of
this one file, never at import time (only one process may load libtpu).
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from jepsen_etcd_demo_tpu.models import CASRegister
from jepsen_etcd_demo_tpu.ops import wgl3, wgl3_pallas
from jepsen_etcd_demo_tpu.ops.limits import limits

MODEL = CASRegister()
# The bench corpus (1024 x 150 ops, 10 processes; BASELINE.json
# configs[4]) encodes at k_slots=12, 128 return steps.
CORPUS_B, CORPUS_R, CORPUS_K = 1024, 128, 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cfg(k):
    cfg = wgl3.dense_config(MODEL, k, 4)
    assert cfg is not None
    return cfg


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _launch_args(B, R, cfg, sharding):
    sp = max(8, (cfg.n_states + 7) // 8 * 8)
    return (_sds((B,), jnp.int32, sharding),
            _sds((B, R), jnp.int32, sharding),
            _sds((B, R, sp, 128), jnp.uint32, sharding))


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_grouped_kernel_at_corpus_shape(one_chip):
    cfg = _cfg(CORPUS_K)
    G = wgl3_pallas.pallas_group_size(cfg, CORPUS_R, CORPUS_B)
    assert G == limits().pallas_group
    launch = wgl3_pallas.local_pallas_launcher_grouped(MODEL, cfg, G)
    hlo = _compile(launch(CORPUS_B, CORPUS_R),
                   *_launch_args(CORPUS_B, CORPUS_R, cfg, one_chip))
    assert hlo.count("tpu_custom_call") >= 1


def test_refused_shape_is_split_by_the_gate(one_chip):
    """1024 histories x 160 steps (k=10) filled 1.02 MiB of the 1 MiB
    SMEM and the chip's compiler refused it. Now pallas_feasible refuses
    it and the launcher runs it as SMEM-sized parts that compile."""
    cfg = _cfg(10)
    B, R = 1024, 160
    G = wgl3_pallas.pallas_group_size(cfg, R, B)
    assert not wgl3_pallas.pallas_feasible(cfg, R, B, group=G)
    parts = wgl3_pallas.launch_parts(B, R, G)
    assert parts == [512, 512]
    assert all(wgl3_pallas.pallas_feasible(cfg, R, b, group=G)
               for b in parts)
    hlo = _compile(wgl3_pallas.local_pallas_launcher_grouped(
        MODEL, cfg, G)(B, R), *_launch_args(B, R, cfg, one_chip))
    assert hlo.count("tpu_custom_call") >= 2


def test_per_history_kernel_at_max_r(one_chip):
    """B=1 at max_r_pallas and max_k_pallas: the K-way prune must not
    nest K deep (a flat lax.switch crashed the compiler at K >= 12)."""
    lim = limits()
    cfg = _cfg(lim.max_k_pallas)
    R = lim.max_r_pallas
    hlo = _compile(wgl3_pallas.local_pallas_launcher(MODEL, cfg)(1, R),
                   *_launch_args(1, R, cfg, one_chip))
    assert "tpu_custom_call" in hlo


def test_grouped_kernel_group_fits_vmem_at_max_k(one_chip):
    cfg = _cfg(limits().max_k_pallas)
    G = wgl3_pallas.pallas_group_size(cfg, 512, 64)
    assert 1 < G < limits().pallas_group
    hlo = _compile(wgl3_pallas.local_pallas_launcher_grouped(
        MODEL, cfg, G)(64, 512), *_launch_args(64, 512, cfg, one_chip))
    assert "tpu_custom_call" in hlo


def test_sparse_resumable_kernel(one_chip):
    cfg = _cfg(limits().max_k_pallas)
    assert wgl3_pallas.pallas_sparse_blocks(cfg) >= 2
    R = 4096
    sp = max(8, (cfg.n_states + 7) // 8 * 8)
    w = 1 << (cfg.k_slots - 5)
    launch = wgl3_pallas.local_pallas_launcher_sparse_resumable(MODEL, cfg)
    hlo = _compile(launch(R),
                   _sds((1,), jnp.int32, one_chip),
                   _sds((1, 8), jnp.int32, one_chip),
                   _sds((1, R), jnp.int32, one_chip),
                   _sds((1, R, sp, 128), jnp.uint32, one_chip),
                   _sds((1, sp, w), jnp.uint32, one_chip),
                   _sds((), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_cycles_tiled_pallas_accumulate(one_chip):
    from jepsen_etcd_demo_tpu.ops import cycles_tiled

    T = cycles_tiled._tile()
    nb, cap = 8, 256
    fn = cycles_tiled._sparse_round_fn(nb, T, cap, use_pallas=True)
    hlo = _compile(fn, _sds((nb, nb, T, T), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_xla_dense_scan_at_corpus_shape(one_chip):
    from jepsen_etcd_demo_tpu.ops.encode import encode_register_history
    from jepsen_etcd_demo_tpu.utils.fuzz import gen_register_history

    import random

    enc = encode_register_history(gen_register_history(
        random.Random(1), n_ops=20, n_procs=4), k_slots=CORPUS_K)
    cfg = _cfg(CORPUS_K)
    _, steps, r = wgl3.batch_steps3([enc], MODEL)
    tabs, act, tgt = (np.asarray(a) for a in wgl3.stack_steps3(steps, r))
    args = [_sds((CORPUS_B, CORPUS_R) + a.shape[2:], a.dtype, one_chip)
            for a in (tabs, act, tgt)]
    hlo = _compile(wgl3.cached_batch_checker3_packed(MODEL, cfg), *args)
    assert "tpu_custom_call" not in hlo


def test_grouped_kernel_sharded_on_four_chips(topo):
    """The --four-chips batch path: the grouped kernel under shard_map
    over a 2x2 v5e mesh, one corpus shard per chip."""
    from jax.sharding import Mesh

    from jepsen_etcd_demo_tpu.parallel.dense import (
        sharded_batch_checker_pallas)

    mesh = Mesh(np.array(topo.devices).reshape(4), ("batch",))
    cfg = _cfg(CORPUS_K)
    G = wgl3_pallas.pallas_group_size(cfg, CORPUS_R, CORPUS_B // 4)
    check = sharded_batch_checker_pallas(MODEL, cfg, mesh, group=G)
    sp = max(8, (cfg.n_states + 7) // 8 * 8)
    sh = lambda *spec: NamedSharding(mesh, P("batch", *spec))  # noqa: E731
    # The launch half of the sharded checker (its prep half is XLA).
    hlo = _compile(check.sharded_launch(CORPUS_B // 4, CORPUS_R),
                   _sds((CORPUS_B,), jnp.int32, sh()),
                   _sds((CORPUS_B, CORPUS_R), jnp.int32, sh(None)),
                   _sds((CORPUS_B, CORPUS_R, sp, 128), jnp.uint32,
                        sh(None, None, None)))
    assert "tpu_custom_call" in hlo
