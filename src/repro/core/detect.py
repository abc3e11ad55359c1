"""Rule classifiers for rain / cicada / silence.

The paper trains a C4.5 tree offline and hard-codes its rules; we keep the
same structure — fixed conjunctions of index thresholds — with constants fit
on the synthetic labelled set (data/synthetic.py), since SERF audio is not
redistributable. The decision *order* and early-exit semantics follow the
paper exactly.
"""
from __future__ import annotations


def detect_rain(idx, cfg):
    """Heavy rain: high broadband power, flat spectrum, flat envelope."""
    return ((idx["psd"] > cfg.rain_psd_min)
            & (idx["flatness"] > cfg.rain_flatness_min)
            & (idx["snr"] < cfg.rain_snr_max))


def detect_cicada(idx, cfg):
    """Cicada chorus: sustained narrowband peak in the cicada band."""
    return ((idx["cicada_peakiness"] > cfg.cicada_peakiness_min)
            & (idx["cicada_band"] > cfg.cicada_band_ratio_min)
            & (idx["cicada_persistence"] > cfg.cicada_persistence_min))


def detect_silence(idx, cfg, threshold=None):
    """Silence: envelope SNR below threshold (paper: the 'lower threshold'
    at 5 s splits was chosen as the operating point)."""
    thr = cfg.silence_snr_threshold if threshold is None else threshold
    return idx["snr"] < thr


def detect_no_activity(idx, cfg, threshold=None):
    """Spectral-flux energy detection (Stowell-style): a chunk with no
    onset — peak rectified flux below threshold — holds no transient
    vocalisation and can be removed. Complements `detect_silence`: flux
    also rejects loud-but-flat chunks whose envelope SNR sneaks over the
    silence threshold."""
    thr = cfg.flux_threshold if threshold is None else threshold
    return idx["flux"] < thr
