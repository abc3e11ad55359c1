"""Sharding rules + multi-device collective tests.

Divisibility validation runs in-process (pure math over all 40 cells).
Actual multi-device lowerings (collective matmul, sharded pipeline) run in
SUBPROCESSES with --xla_force_host_platform_device_count, because tests in
this process must keep seeing 1 CPU device (per the brief)."""
import subprocess
import sys
import textwrap

import pytest

from repro.configs import ARCHS, SHAPES, cell_is_runnable
from repro.distributed.sharding import ShardingRules, _TABLES


def test_rules_resolution_no_mesh():
    r = ShardingRules(mesh=None)
    assert r.constrain(1.0, "batch") == 1.0
    assert r.sharding("batch") is None


def test_rules_tables_complete():
    for mode, table in _TABLES.items():
        for name, axes in table.items():
            assert isinstance(axes, tuple), (mode, name)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_all_cells_shard_evenly(arch):
    """Static divisibility audit for every (arch x shape) cell on the 16x16
    and 2x16x16 meshes — catches sharding mismatch before any compile."""
    cfg = ARCHS[arch]
    for tp in (16,):
        assert cfg.q_dim % tp == 0, "q_dim"
        assert cfg.kv_dim % tp == 0, "kv_dim"
        if cfg.d_ff:
            assert cfg.d_ff % tp == 0, "d_ff"
        assert cfg.padded_vocab % tp == 0, "vocab"
        assert cfg.d_model % 32 == 0, "fsdp d_model over pod*data"
    for shape in SHAPES.values():
        ok, _ = cell_is_runnable(cfg, shape)
        if not ok:
            continue
        if shape.kind in ("train", "prefill"):
            assert shape.global_batch % 32 == 0 or shape.global_batch % 16 == 0
        elif shape.global_batch > 1:
            assert shape.global_batch % 32 == 0
            assert shape.seq_len % 16 == 0      # kv_seq over model
        else:
            assert shape.seq_len % 256 == 0     # kv_seq over data x model


_SUBPROCESS_TEMPLATE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import _make_mesh
{body}
print("SUBPROC_OK")
"""


def _run_subprocess(body):
    code = _SUBPROCESS_TEMPLATE.format(body=textwrap.dedent(body))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROC_OK" in out.stdout


def test_collective_matmul_matches_einsum():
    _run_subprocess("""
    from repro.distributed.collective_matmul import ag_matmul
    mesh = _make_mesh((4,), ("model",))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(8, 12).astype(np.float32))
    got = jax.jit(lambda a, b: ag_matmul(a, b, mesh))(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)
    """)


def test_pipeline_sharded_matches_single_device():
    """The audio pipeline gives identical masks under 4-way data
    parallelism (the paper's distribution-invariance requirement)."""
    _run_subprocess("""
    from repro.configs import SERF_AUDIO as cfg
    from repro.core.plans import Preprocessor
    from repro.data.synthetic import generate_labelled
    from repro.distributed.sharding import ShardingRules
    audio, labels = generate_labelled(3, 4*12, segment_s=5.0)
    S5 = audio.shape[-1]
    chunks = (audio.reshape(4, 12, 2, S5).transpose(0, 2, 1, 3)
              .reshape(4, 2, 12*S5))
    mesh = _make_mesh((4, 1), ("data", "model"))
    rules = ShardingRules(mesh)
    x = jax.device_put(jnp.asarray(chunks),
                       NamedSharding(mesh, P("data", None, None)))
    with mesh:
        det_sh = Preprocessor(cfg, rules).detect(x)
    det_1 = Preprocessor(cfg).detect(jnp.asarray(chunks))
    np.testing.assert_array_equal(np.asarray(det_sh.keep),
                                  np.asarray(det_1.keep))
    np.testing.assert_allclose(np.asarray(det_sh.wave5),
                               np.asarray(det_1.wave5), atol=2e-4)
    """)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_async_plan_sharded_matches_single_device(mode):
    """The data-parallel main path on 4 devices — the stage chain and the
    fused survivor tail under shard_map (`graph.per_chunk`), the tail's
    batch gathered to every device and its survivor list split — gives
    the same survivor sets and cleaned audio as one device."""
    _run_subprocess(f"""
    from repro.configs import SERF_AUDIO as cfg
    from repro.core.plans import Preprocessor
    from repro.data.loader import audio_batch_maker
    from repro.distributed.sharding import NULL_RULES, ShardingRules
    from repro.kernels import backend
    make = audio_batch_maker(seed=25, batch_long_chunks=4)
    stream = [(w, (make(w)[0], None)) for w in range(2)]
    rules = ShardingRules(_make_mesh((4, 1), ("data", "model")))
    with backend.use({mode!r}):
        sh = list(Preprocessor(cfg, rules, plan="async", depth=2,
                               pad_multiple=4).run(stream))
        one = list(Preprocessor(cfg, NULL_RULES, plan="async", depth=2,
                                pad_multiple=1).run(stream))
    assert sum(r.n_kept for r in one) > 0
    for a, b in zip(sh, one, strict=True):
        np.testing.assert_array_equal(np.asarray(a.det.keep),
                                      np.asarray(b.det.keep))
        # same kernels and per-row arithmetic: only XLA's fusion of the
        # jnp code around them may change with the per-device shape
        np.testing.assert_allclose(a.cleaned, b.cleaned, rtol=0, atol=1e-5)
    """)


def test_train_step_sharded_matches_single_device():
    """One TP+DP train step == single-device step (tiny f32 model)."""
    _run_subprocess("""
    import dataclasses
    from repro.configs import ARCHS, reduced
    from repro.models.zoo import build_model
    from repro.distributed.sharding import ShardingRules, tree_shardings
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import (make_train_step, init_train_state,
                                        train_state_specs)
    cfg = dataclasses.replace(reduced(ARCHS["llama3.2-3b"]), dtype="float32")
    model = build_model(cfg)
    opt = OptConfig(lr=1e-2)
    params, state = init_train_state(model, opt, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}
    from repro.distributed.sharding import NULL_RULES
    p1, s1, m1 = jax.jit(make_train_step(model, NULL_RULES, opt))(
        params, state, batch)
    mesh = _make_mesh((2, 2), ("data", "model"))
    rules = ShardingRules(mesh)
    pspecs, ospecs = train_state_specs(model, opt)
    p_sh = tree_shardings(rules, pspecs)
    o_sh = tree_shardings(rules, ospecs)
    with mesh:
        step = jax.jit(make_train_step(model, rules, opt),
                       in_shardings=(p_sh, o_sh, None),
                       out_shardings=(p_sh, o_sh, None))
        p2, s2, m2 = step(params, state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p1, p2)))
    assert d < 1e-3, d
    """)
