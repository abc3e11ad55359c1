"""With the timed path broken underneath, a run of the harness on the CPU
comes out not correct: once for each fault a cell can have."""
import os
import subprocess
import sys

import pytest

import rehearse


def _tail_returns_its_input(monkeypatch):
    """The survivor tail hands back its gathered rows, undenoised."""
    import jax.numpy as jnp
    from repro.kernels.fused_tail import ops
    monkeypatch.setattr(ops, "fused_tail", lambda w, i, cfg, hpf=False: (
        jnp.take(w, i, axis=0, mode="fill", fill_value=0.0)))


def _half_the_survivors_left_out(monkeypatch):
    """The tail cleans the first half of its survivors; the rest come back
    as the pad rows."""
    import jax.numpy as jnp
    from repro.kernels.fused_tail import ops
    tail = ops.fused_tail

    def half(w, i, cfg, hpf=False):
        n = i.shape[0]
        return tail(w, jnp.where(jnp.arange(n) < n // 2, i, w.shape[0]),
                    cfg, hpf=hpf)
    monkeypatch.setattr(ops, "fused_tail", half)


def _keep_altered(monkeypatch):
    """The keep mask of each batch's first 5 s chunk is flipped where the
    removal point produces it."""
    from repro.core import graph
    apply = graph.RemovalPoint.apply

    def flipped(self, state, rules):
        state = apply(self, state, rules)
        state["keep"] = state["keep"].at[0].set(~state["keep"][0])
        return state
    monkeypatch.setattr(graph.RemovalPoint, "apply", flipped)


def _compress_returns_its_input(monkeypatch):
    """The band-pass FIR is skipped: the mono audio is only decimated."""
    from repro.core import stages
    monkeypatch.setattr(stages, "compress", lambda x, cfg: x[:, ::2])


FAULTS = [_tail_returns_its_input, _half_the_survivors_left_out,
          _keep_altered, _compress_returns_its_input]
CASES = [("archive_mixed", f) for f in FAULTS]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_checkout(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__[1:]}" for w, f in CASES])
def test_a_broken_path_is_not_correct(root, workload, fault, monkeypatch):
    fault(monkeypatch)
    rc, last, out, err = rehearse.run_cell(root, workload)
    assert rc == 0 and last is not None, err[-2000:]
    assert last["correct"] is False, last["check"]


FOUR_DEVICES = r"""
import json, sys
import rehearse
from repro.core import graph
root = rehearse.tiny_checkout(sys.argv[1])
name = rehearse.add_split_cell(root, "archive_mixed", 4)
_, ok, _, _ = rehearse.run_cell(root, name)
per_chunk = graph.per_chunk
graph.per_chunk = lambda rules, fn, *a, replicated=(): per_chunk(rules, fn, *a)
_, broken, _, _ = rehearse.run_cell(root, name)
print(json.dumps([ok, broken]))
"""


def test_four_devices_correct_and_the_exchange_left_out_not(tmp_path):
    """On four CPU devices a four-chip copy of a cell, its batch split over
    them, proves correct; without the tail's all-gather of the batch (each
    device gathering survivors from its own quarter) it does not."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(rehearse.ROOT, "src"),
                                           here, rehearse.BENCH]))
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    import json
    ok, broken = json.loads(p.stdout.strip().splitlines()[-1])
    assert ok["correct"] is True and ok["device"]["count"] == 4, ok
    assert broken["correct"] is False, broken["check"]
