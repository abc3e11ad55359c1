"""Compile every Pallas kernel of the main path for a TPU v5e at the SERF
geometry, without a chip: the TPU compiler is installed, and a described
`v5e:2x2` topology gives it a target. What it refuses here (block shapes off
the (8, 128) tiling, unaligned in-kernel slices, too much scoped VMEM)
would be refused on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so the call must happen in the
test worker that runs this file. The persistent compilation cache is off
around these compiles — an entry written for a described chip cannot be
read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import SERF_AUDIO as cfg
from repro.core.plans import Preprocessor
from repro.kernels import backend
from repro.kernels.fir_hpf import kernel as FK
from repro.kernels.fir_hpf import ref as FR
from repro.kernels.fused_tail import kernel as FTK
from repro.kernels.fused_tail import ops as FTO
from repro.kernels.mmse_stsa import kernel as MK
from repro.kernels.stft_dft import kernel as SK
from repro.kernels.stft_dft import ops as SO

S5 = cfg.final_split_samples          # 5 s final split @ 22.05 kHz
S15 = cfg.detect_split_samples        # 15 s detection split @ 22.05 kHz
S60 = cfg.long_split_samples          # 60 s long chunk @ 44.1 kHz
F32, I32 = jnp.float32, jnp.int32

HPF_TAPS = FR.highpass_taps(cfg.hpf_cutoff_hz, cfg.target_rate_hz,
                            cfg.hpf_taps)
BAND_TAPS = FR.bandpass_decimate_taps(
    cfg.hpf_cutoff_hz, cfg.target_rate_hz / 2.0, cfg.source_rate_hz,
    cfg.hpf_taps)

# name -> (function, argument shapes): the shapes each kernel meets on the
# main path (8 detection / final rows, a 2-chunk compress batch, the fused
# tail's 16-row survivor bucket out of a 24-chunk batch)
CASES = {
    "fir_hpf_stride1": (lambda x: FK.fir_pallas(x, HPF_TAPS, 1),
                        [((8, S5), F32)]),
    "fir_hpf_stride2": (lambda x: FK.fir_pallas(x, BAND_TAPS, 2),
                        [((2, S60), F32)]),
    "stft_dft": (lambda x: SK.stft_pallas(SO.pad_for_stft(x)),
                 [((8, S15), F32)]),
    "mmse_stsa": (lambda p, n: MK.mmse_gain_pallas(p, n),
                  [((8, 896, 256), F32), ((8, 256), F32)]),
    "fused_tail": (lambda w, i: FTK.fused_tail_pallas(
        w, i, cfg, vmem_limit_bytes=FTO.VMEM_BUDGET),
        [((24, S5), F32), ((16,), I32)]),
    "fused_tail_hpf": (lambda w, i: FTK.fused_tail_pallas(
        w, i, cfg, hpf=True, vmem_limit_bytes=FTO.VMEM_BUDGET),
        [((24, S5), F32), ((16,), I32)]),
}


@pytest.fixture(scope="module")
def topo():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"    # no compiler logs off-tree
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture(scope="module")
def compile_for_v5e(topo):
    """jit(fn).lower(shapes on one described chip).compile(), with the
    persistent compilation cache off for the module's compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    one_chip = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(compile_for_v5e, case):
    fn, shapes = CASES[case]
    text = compile_for_v5e(fn, shapes).as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("hpf", [False, True])
@pytest.mark.parametrize("tc", FTO.CANDIDATES, ids=lambda tc: f"fb{tc.frame_block}")
def test_fused_tail_vmem_model_is_enough(compile_for_v5e, tc, hpf):
    """Every candidate the autotuner may pick at the SERF row length
    compiles with its scoped-VMEM limit set to the model's own figure."""
    need = FTO.vmem_bytes(tc, S5, cfg.stft_window, cfg.stft_hop, hpf)
    if need > FTO.VMEM_BUDGET:
        assert tc not in FTO.feasible(S5, cfg.stft_window, cfg.stft_hop,
                                      hpf)
        return
    compile_for_v5e(
        lambda w, i: FTK.fused_tail_pallas(w, i, cfg, hpf, tc.frame_block,
                                           vmem_limit_bytes=need),
        [((24, S5), F32), ((16,), I32)])


def test_scoped_fused_tail_keeps_its_kernel_name(compile_for_v5e, monkeypatch):
    """The tail program as the plans jit it, `PipelineGraph.
    tail_indexed_fused` with its name scopes, at the SERF geometry: the
    program reads `jit_tail_idx_fused`, the Pallas call is still the
    instruction `fused_tail.<n>` that a device trace names the kernel by,
    and the kernel and the resynthesis carry their scopes."""
    # what the kernels resolve to on the chip: compiled Pallas
    monkeypatch.setattr(backend, "resolve", lambda: (True, False))
    fn = Preprocessor(cfg, plan="async").phase_fn("tail_idx_fused")
    text = compile_for_v5e(fn, [((24, S5), F32), ((16,), I32)]).as_text()
    assert text.startswith("HloModule jit_tail_idx_fused,")
    calls = re.findall(r"^\s*%(\S+) = .*custom_call_target=\"tpu_custom_call\""
                       r".*op_name=\"([^\"]*)\"", text, re.M)
    assert [re.sub(r"\.\d+$", "", c) for c, _ in calls] == ["fused_tail"]
    assert "/fused_tail/kernel/" in calls[0][1]
    assert re.search(r'op_name="jit\(tail_idx_fused\)/fused_tail/istft/', text)


def test_four_chip_tail_gathers_once_in_its_scope(topo, compile_for_v5e,
                                                  monkeypatch):
    """The tail program of a batch split over the 2x2 host, as the plans
    jit it: the batch is all-gathered to every chip once, by the explicit
    collective under the `gather` scope, and nothing else crosses chips."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.distributed.sharding import ShardingRules
    monkeypatch.setattr(backend, "resolve", lambda: (True, False))
    rules = ShardingRules(Mesh(np.array(topo.devices).reshape(4, 1),
                               ("data", "model")))
    fn = Preprocessor(cfg, rules, plan="async",
                      pad_multiple=4).phase_fn("tail_idx_fused")
    w = jax.ShapeDtypeStruct((96, S5), F32,
                             sharding=rules.sharding("chunks", None))
    i = jax.ShapeDtypeStruct((16,), I32, sharding=rules.sharding("chunks"))
    text = jax.jit(fn).lower(w, i).compile().as_text()
    colls = re.findall(r"^\s*%\S+ = \S+ (all-gather|all-reduce|all-to-all|"
                       r"collective-permute|reduce-scatter)(?:-start)?\("
                       r".*op_name=\"([^\"]*)\"", text, re.M)
    assert [c for c, _ in colls] == ["all-gather"], colls
    assert colls[0][1].startswith("jit(tail_idx_fused)/fused_tail/")
    assert "/gather/" in colls[0][1]
