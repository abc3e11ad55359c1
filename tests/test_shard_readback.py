"""The cleaned batch of a mesh, split by rows over its devices, is read back
shard by shard on threads of the plan's own: the same bytes as JAX's
whole-array `np.asarray`, a fresh array every batch, `d2h_shards` counting
the shards read apart (0 where the array is read whole), and one
`d2h_shard` span per shard. Where every shard's host value is in column
order the result takes that order, and its copy is split by column blocks
(`d2h_col_blocks`, one `d2h_block_copy` span each).

Multi-device cases run in a subprocess with four CPU devices, so this
process keeps seeing one; the column-order path runs here, on stand-in
shards whose host values are column-ordered numpy arrays."""
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import numpy as np
import pytest

from repro.configs import SERF_AUDIO
from repro.core.plans import Preprocessor, _PendingTail, _shard_reads
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rows(lo, hi):
    return (slice(lo, hi), slice(None))


FOUR = [_rows(16 * k, 16 * (k + 1)) for k in range(4)]


@pytest.mark.parametrize("indices, n_real, want", [
    (FOUR, 5, [(0, 0, 0, 5)]),                                # first shard
    (FOUR, 32, [(0, 0, 0, 16), (1, 1, 16, 32)]),              # at a boundary
    (FOUR, 57, [(0, 0, 0, 16), (1, 1, 16, 32), (2, 2, 32, 48),
                (3, 3, 48, 57)]),                             # last shard
    (FOUR, 64, [(0, 0, 0, 16), (1, 1, 16, 32), (2, 2, 32, 48),
                (3, 3, 48, 64)]),                             # whole bucket
    (FOUR, 20, [(0, 0, 0, 16), (1, 1, 16, 20)]),              # padding tail
    (FOUR[::-1], 40, [(3, 0, 0, 16), (2, 1, 16, 32),
                      (1, 2, 32, 40)]),                       # any order
    ([(slice(0, 32, 1), slice(0, 8, 1)), _rows(32, 64)], 64,
     [(0, 0, 0, 32), (1, 1, 32, 64)]),                        # explicit whole
    ([_rows(0, 64)], 64, None),                               # one shard
    ([_rows(0, 64)] * 4, 64, None),                           # replicated
    (FOUR[:2] * 2, 64, None),                                 # rows repeated
    ([FOUR[0], FOUR[2], FOUR[3]], 64, None),                  # a gap
    ([(slice(None), slice(0, 4)), (slice(None), slice(4, 8))], 64,
     None),                                                   # by columns
    ([(slice(0, 32), slice(0, 4)), (slice(0, 32), slice(4, 8)),
      (slice(32, 64), slice(0, 4)), (slice(32, 64), slice(4, 8))], 64,
     None),                                                   # both axes
    ([(slice(0, 64, 2), slice(None)), (slice(1, 64, 2), slice(None))], 64,
     None),                                                   # strided
], ids=["first", "boundary", "last", "whole", "padding_tail", "any_order",
        "explicit_whole", "one_shard", "replicated", "rows_repeated", "gap",
        "by_columns", "both_axes", "strided"])
def test_shard_reads_clip_each_row_range_to_the_real_rows(indices, n_real,
                                                          want):
    assert _shard_reads(indices, (64, 8), n_real) == want


FOUR_DEVICES = r"""
import sys, threading
import jax, numpy as np
from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import SHARD_THREAD, Preprocessor, _PendingTail
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_local_mesh
from repro.obs import tracing as obs_tracing

rules = ShardingRules(make_local_mesh())
assert len(rules.mesh.devices.flat) == 4
plan = Preprocessor(cfg, rules, plan="async", pad_multiple=4).plan
rng = np.random.default_rng(5)


def readback(sharding, n_real, wid):
    x = jax.device_put(rng.standard_normal((64, 96)).astype(np.float32),
                       sharding)
    x.copy_to_host_async()                    # as the tail's dispatch does
    pend = _PendingTail(None, x, n_real, wid, None, 0, {"d2h_bytes": 0})
    got = plan._readback(pend)
    want = np.asarray(x)[:n_real]
    assert got.dtype == want.dtype and got.shape == want.shape, (n_real,)
    assert (got.view(np.uint32) == want.view(np.uint32)).all(), n_real
    assert pend.timings["d2h_bytes"] == x.nbytes
    assert pend.timings["d2h_col_blocks"] == 0   # CPU host values: rows
    return got, pend.timings["d2h_shards"]


def threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(SHARD_THREAD)]


tracer = obs_tracing.Tracer()
obs_tracing.set_tracer(tracer)
rows = rules.sharding("chunks", None)
for wid, n_real in enumerate((5, 32, 57, 64, 20)):
    _, shards = readback(rows, n_real, wid)
    n = -(-n_real // 16)                      # shards holding real rows
    assert shards == n, (n_real, shards)
    for name in ("d2h_shard", "d2h_shard_copy"):
        got = sorted(e["args"]["shard"] for e in tracer.events
                     if e["ph"] == "B" and e["name"] == name
                     and e["args"]["wid"] == wid)
        assert got == list(range(n)), (name, n_real, got)
obs_tracing.validate_chrome_trace(tracer.chrome())
outer = {e["tid"] for e in tracer.events if e["name"] == "d2h_wait"}
inner = {e["tid"] for e in tracer.events if e["name"] == "d2h_shard"}
assert inner and not inner & outer, (inner, outer)
obs_tracing.set_tracer(None)

a, _ = readback(rows, 64, 10)
b, _ = readback(rows, 64, 11)
assert not np.shares_memory(a, b)

switch = sys.getswitchinterval()
sys.setswitchinterval(1e-6)                   # threads interleave finely
try:
    for wid in range(30, 70):
        readback(rows, 64 - wid % 20, wid)    # bitwise, every time
finally:
    sys.setswitchinterval(switch)

for sharding in (jax.sharding.SingleDeviceSharding(jax.devices()[0]),
                 rules.sharding(None, None)):         # fully replicated
    assert readback(sharding, 57, 20)[1] == 0

assert 0 < len(threads()) <= 4, threads()
plan._close_shard_pool()
assert not threads(), threads()
print("ok")
"""


def test_four_devices_read_shards_apart_bitwise_as_the_whole_array():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(FOUR_DEVICES)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == "ok"


class _Split:
    """Stands in for a device array split by rows into four shards of 16,
    each shard's `.data` its host value (`F`: column order, `C`: row order,
    one letter a shard in row order), listed in `order`."""
    is_fully_replicated = False

    def __init__(self, rng, orders, order=range(4)):
        rows = [rng.standard_normal((16, 96)).astype(np.float32)
                for _ in orders]
        host = {"F": np.asfortranarray, "C": np.ascontiguousarray}
        self.whole = np.concatenate(rows)
        self.shape, self.dtype, self.nbytes = (self.whole.shape,
                                               self.whole.dtype,
                                               self.whole.nbytes)
        self.addressable_shards = [SimpleNamespace(
            index=(slice(16 * k, 16 * (k + 1)), slice(None)),
            data=host[orders[k]](rows[k])) for k in order]

    def block_until_ready(self):
        return self


@pytest.fixture
def plan():
    p = Preprocessor(SERF_AUDIO, plan="async").plan
    yield p
    p._close_shard_pool()


@pytest.fixture
def tracer():
    t = obs_tracing.Tracer()
    obs_tracing.set_tracer(t)
    yield t
    obs_tracing.set_tracer(None)


def _readback(plan, out, n_real, wid=0):
    """`cleaned` and the timings, checked bitwise against the rows."""
    pend = _PendingTail(None, out, n_real, wid, None, 0, {"d2h_bytes": 0})
    got = plan._readback(pend)
    want = out.whole[:n_real]
    assert got.dtype == want.dtype and got.shape == want.shape, n_real
    assert (got.view(np.uint32) == want.view(np.uint32)).all(), n_real
    return got, pend.timings


def _spans(tracer, name, arg):
    return sorted(e["args"][arg] for e in tracer.events
                  if e["ph"] == "B" and e["name"] == name)


@pytest.mark.parametrize("n_real, order", [
    (5, range(4)), (32, range(4)), (57, range(4)), (64, range(4)),
    (20, range(4)), (40, range(3, -1, -1))],
    ids=["first", "boundary", "last", "whole", "padding_tail", "any_order"])
def test_column_ordered_shards_copy_by_column_blocks(plan, tracer, n_real,
                                                     order):
    out = _Split(np.random.default_rng(n_real), "FFFF", order)
    got, t = _readback(plan, out, n_real)
    assert got.flags.f_contiguous and not got.flags.c_contiguous
    assert t["d2h_shards"] == -(-n_real // 16)
    assert t["d2h_col_blocks"] == plan._shard_pool._max_workers == 4
    assert _spans(tracer, "d2h_block_copy", "block") == [0, 1, 2, 3]
    assert _spans(tracer, "d2h_shard", "shard") == list(range(-(-n_real
                                                                // 16)))
    assert _spans(tracer, "d2h_shard_copy", "shard") == []
    obs_tracing.validate_chrome_trace(tracer.chrome())


@pytest.mark.parametrize("orders", ["CCCC", "FCFF", "CFFF"],
                         ids=["rows", "mixed", "mixed_first"])
def test_row_or_mixed_order_shards_copy_shard_by_shard(plan, tracer, orders):
    got, t = _readback(plan, _Split(np.random.default_rng(7), orders), 57)
    assert got.flags.c_contiguous
    assert t["d2h_shards"] == 4 and t["d2h_col_blocks"] == 0
    assert _spans(tracer, "d2h_shard_copy", "shard") == [0, 1, 2, 3]
    assert _spans(tracer, "d2h_block_copy", "block") == []


def test_column_ordered_batches_share_no_memory(plan):
    rng = np.random.default_rng(11)
    outs = [_Split(rng, "FFFF") for _ in range(2)]
    a, b = (_readback(plan, out, 64, wid)[0] for wid, out in enumerate(outs))
    assert not np.shares_memory(a, b)
    for s in outs[0].addressable_shards:
        assert not np.shares_memory(a, s.data)


def test_column_blocks_bitwise_while_threads_interleave(plan):
    rng = np.random.default_rng(13)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)               # threads interleave finely
    try:
        for wid in range(30, 70):
            _readback(plan, _Split(rng, "FFFF"), 64 - wid % 20, wid)
    finally:
        sys.setswitchinterval(switch)


def test_column_blocks_reach_the_metrics_registry(plan):
    prev = obs_metrics.get_registry()
    obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    try:
        for wid, orders in enumerate(("FFFF", "CCCC", "FFFF")):
            pend = _PendingTail(SimpleNamespace(keep=np.ones(3, bool),
                                                stats={}),
                                _Split(np.random.default_rng(wid), orders),
                                57, wid, None, 0, {"d2h_bytes": 0})
            plan._emit(pend, plan._readback(pend))
        snap = obs_metrics.snapshot()["plan_d2h_col_blocks_total"]
    finally:
        obs_metrics.set_registry(prev)
    assert snap["series"][0]["value"] == 8
