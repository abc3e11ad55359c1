"""The fused tail's exchange on a mesh: an explicit all-gather of the batch
under the `gather` scope, which gives the same survivor rows and cleaned
audio as one device and as the plain reference (`bench/reference.py`), and
the two counters of what the mesh adds: `gather_bytes`, what each device
receives in that all-gather, and `kept_skew`, how unevenly the survivors
fall over the devices' own chunks.

Multi-device cases run in a subprocess with four CPU devices, so this
process keeps seeing one."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import SERF_AUDIO as cfg
from repro.core import scheduler as SCHED
from repro.core.plans import Preprocessor
from repro.data.loader import audio_batch_maker
from repro.obs import metrics as obs_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("keep, parts, skew", [
    ([1, 0, 1, 1, 0, 0], 1, 1.0),                       # one device
    ([0] * 8, 4, 1.0),                                  # nothing kept
    ([1, 1] * 4, 4, 1.0),                               # even
    ([1, 1, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 0, 0, 1], 4, 2.0),
    ([0, 0, 0, 1, 0, 0, 0, 0], 4, 4.0),                 # all on one
], ids=["one_device", "none_kept", "even", "counts_2_0_4_2", "one_part"])
def test_kept_skew_is_the_busiest_part_over_the_mean(keep, parts, skew):
    assert SCHED.kept_skew(np.array(keep, bool), parts) == skew


@pytest.fixture
def fresh_registry():
    prev = obs_metrics.get_registry()
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    yield reg
    obs_metrics.set_registry(prev)


def test_one_device_gathers_nothing_and_is_even(fresh_registry):
    make = audio_batch_maker(seed=31, batch_long_chunks=1)
    stream = [(w, (make(w)[0], None)) for w in range(3)]
    pre = Preprocessor(cfg, plan="async", depth=2)
    res = list(pre.run(stream))
    assert sum(r.n_kept for r in res) > 0
    for r in res:
        assert r.timings["gather_bytes"] == 0
        assert r.timings["kept_skew"] == 1.0
    snap = obs_metrics.snapshot()
    assert snap["plan_gather_bytes_total"]["series"][0]["value"] == 0
    assert snap["plan_kept_skew"]["series"][0]["count"] == 3


FOUR_DEVICES = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
import gen, reference
from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import Preprocessor
from repro.distributed.sharding import NULL_RULES, ShardingRules
from repro.launch.mesh import make_local_mesh
from repro.obs import metrics as obs_metrics

with open(sys.argv[1]) as f:
    g = json.load(f)["geometry"]
with open(sys.argv[2]) as f:
    traffic = json.load(f)
x, _ = gen.long_chunks(7, traffic, g, 4)
stream = [(0, x), (1, x[::-1].copy())]
rules = ShardingRules(make_local_mesh())
sh = list(Preprocessor(cfg, rules, plan="async", depth=2,
                       pad_multiple=4).run(stream))
one = list(Preprocessor(cfg, NULL_RULES, plan="async", depth=2).run(stream))
ref = reference.Reference(g)
worst = 0.0
for (_, xb), a, b in zip(stream, sh, one, strict=True):
    r = ref.run(xb)
    keep = np.asarray(a.det.keep)
    assert (keep == np.asarray(b.det.keep)).all()
    assert (keep == r["keep"]).all()
    assert a.n_kept == keep.sum() > 0
    # same kernels and per-row arithmetic: only XLA's fusion of the jnp
    # code around them may change with the per-device shape
    np.testing.assert_allclose(a.cleaned, b.cleaned, rtol=0, atol=1e-5)
    d = a.cleaned.astype(np.float64) - r["cleaned"]
    worst = max(worst, float(np.sqrt((d ** 2).sum()
                                     / (r["cleaned"] ** 2).sum())))
    t = a.timings
    assert t["gather_bytes"] == a.det.wave5.nbytes * 3 // 4
    counts = keep.reshape(4, -1).sum(1)
    assert t["kept_skew"] == counts.max() / counts.mean()
    assert b.timings["gather_bytes"] == 0 and b.timings["kept_skew"] == 1.0
    # the cleaned rows come back shard by shard where a shard holds any
    assert t["d2h_shards"] == -(-a.n_kept // (t["tail_rows"] // 4)) > 1
    assert b.timings["d2h_shards"] == 0
snap = obs_metrics.snapshot()["plan_gather_bytes_total"]["series"]
assert snap[0]["value"] == sum(a.timings["gather_bytes"] for a in sh)

# the compiled 4-way tail: one all-gather, in the `gather` scope; none
# on one device
w = jax.ShapeDtypeStruct(sh[0].det.wave5.shape, jnp.float32,
                         sharding=rules.sharding("chunks", None))
i = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=rules.sharding("chunks"))
colls = {}
for name, pre, args in (
        ("four", Preprocessor(cfg, rules, plan="async", pad_multiple=4),
         (w, i)),
        ("one", Preprocessor(cfg, plan="async"),
         (jax.ShapeDtypeStruct(w.shape, w.dtype),
          jax.ShapeDtypeStruct(i.shape, i.dtype)))):
    text = jax.jit(pre.phase_fn("tail_idx_fused")).lower(
        *args).compile().as_text()
    colls[name] = [ln for ln in text.splitlines()
                   if " = " in ln and any(c in ln.split(" = ", 1)[1].split(
                       "(", 1)[0] for c in ("all-gather", "all-reduce",
                                            "all-to-all",
                                            "collective-permute"))]
print(json.dumps({"worst": worst, "colls": colls}))
"""


def test_four_devices_gather_explicitly_and_agree_with_the_reference():
    bench = os.path.join(ROOT, "bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), bench]))
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(FOUR_DEVICES),
         os.path.join(bench, "configs", "serf_archive.json"),
         os.path.join(bench, "traffic", "mixed.json")],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    # within the archive cells' limit on cleaned_rel_rms (PERF.md §2)
    assert out["worst"] < 1.5e-6, out
    assert out["colls"]["one"] == []
    (line,) = out["colls"]["four"]
    assert "all-gather" in line
    assert 'op_name="jit(tail_idx_fused)/fused_tail/shard_map/gather/' in line
