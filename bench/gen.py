"""Seeded SERF-like long chunks for the benchmark cells.

The segment generators are a copy of `repro.data.synthetic` (bird chirps,
heavy rain, cicada chorus, silence over a low stationary background, stereo
44.1 kHz), kept here so that no later change to the program moves the
benchmark's inputs. A long chunk is 12 five-second segments laid end to end
per channel, the layout of `repro.data.loader.audio_batch_maker`.

Two departures make set-up cheap and every seed do the same work:

- Each label has a bank of `segments_per_label` distinct segments, and a
  long chunk draws its segments from the banks of its labels, so the host
  synthesises a few dozen segments instead of one per position.
- Each group of `group` long chunks (an archive cell's batch) holds each
  label exactly `round(p * n)` times (largest remainder), laid out in runs
  whose lengths follow the generator's persistence (geometric, mean
  1 / (1 - persistence)), the runs in a seeded order. The seed changes the
  order and the content, not the label counts, so every seed gives every
  batch the same amount of survivor work.

`graded_batches` makes the set-up's warm-up batches: bird song and silence
in the proportions that reach each survivor bucket of the tail.
"""
from __future__ import annotations

import numpy as np

LABELS = ("bird", "rain", "cicada", "silence")


def rng_for(seed, *purpose):
    """A generator for one purpose of one seed; any whole number seeds it."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64, *purpose]))


def _chirp(rng, rate):
    dur = int(rate * rng.uniform(0.05, 0.4))
    f0 = rng.uniform(2000, 6000)
    f1 = f0 * rng.uniform(0.7, 1.6)
    freq = np.linspace(f0, min(f1, 10_000), dur)
    phase = 2 * np.pi * np.cumsum(freq) / rate
    return (np.sin(phase) * np.hanning(dur)).astype(np.float32)


def _bird(rng, n, rate, density=3.0):
    x = np.zeros(n, np.float32)
    for _ in range(max(1, rng.poisson(density * n / rate))):
        c = _chirp(rng, rate)
        start = rng.integers(0, max(1, n - len(c)))
        x[start:start + len(c)] += rng.uniform(0.15, 0.6) * c
    return x


def _bandnoise(rng, n, rate, lo, hi):
    spec = np.fft.rfft(rng.standard_normal(n, np.float32))
    f = np.fft.rfftfreq(n, 1.0 / rate)
    return np.fft.irfft(spec * ((f >= lo) & (f <= hi)), n).astype(np.float32)


def _rain(rng, n, rate):
    x = 0.35 * _bandnoise(rng, n, rate, 300, 16_000)
    d = int(rate * 0.004)
    for _ in range(rng.poisson(30 * n / rate)):
        start = rng.integers(0, n - d)
        x[start:start + d] += rng.uniform(0.2, 0.6) * np.hanning(d).astype(
            np.float32)
    return x


def _cicada(rng, n, rate):
    f0 = rng.uniform(3800, 6500)
    x = 0.5 * _bandnoise(rng, n, rate, f0 - 250, f0 + 250)
    am = 1.0 + 0.3 * np.sin(2 * np.pi * rng.uniform(8, 15)
                            * np.arange(n) / rate)
    x = x * am.astype(np.float32)
    if rng.random() < 0.3:
        x += 0.3 * _bird(rng, n, rate, density=1.0)
    return x


def _silence(rng, n, rate):
    return np.zeros(n, np.float32)


_SEGMENT = {"bird": _bird, "rain": _rain, "cicada": _cicada,
            "silence": _silence}


def segment(rng, label, n, rate, background_level):
    """One (2, n) stereo segment of `label` over the background noise."""
    x = _SEGMENT[label](rng, n, rate)
    x = x + background_level * rng.standard_normal(n, np.float32)
    return np.stack([x, x + 0.003 * rng.standard_normal(n, np.float32)])


def label_counts(probs, n):
    """Exact per-label counts summing to n (largest remainder)."""
    p = np.asarray(probs, np.float64)
    raw = p / p.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def label_sequence(rng, probs, persistence, n):
    """n label indices with exact counts, in runs of mean length
    1 / (1 - persistence), the runs in a seeded order."""
    runs = []
    for label, count in enumerate(label_counts(probs, n)):
        left = int(count)
        while left > 0:
            k = min(left, int(rng.geometric(1.0 - persistence)))
            runs.append(np.full(k, label, np.int32))
            left -= k
    order = rng.permutation(len(runs))
    return np.concatenate([runs[i] for i in order]) if runs else \
        np.zeros(0, np.int32)


def _segment_bank(rng, labels, traffic, geometry):
    """{label index: (segments_per_label, 2, n) distinct segments}."""
    rate = int(geometry["source_rate_hz"])
    n = int(round(geometry["final_split_s"] * rate))
    return {li: np.stack([segment(rng, LABELS[li], n, rate,
                                  traffic["background_level"])
                          for _ in range(int(traffic["segments_per_label"]))])
            for li in labels}


def assemble(rng, bank, labels):
    """(n_long, 2, S_long) float32 long chunks whose 5 s segments carry
    `labels` (n_long, per_long), each drawn from its label's bank."""
    n_long, per_long = labels.shape
    n = next(iter(bank.values())).shape[-1]
    out = np.empty((n_long, 2, per_long * n), np.float32)
    for i in range(n_long):
        for j in range(per_long):
            b = bank[int(labels[i, j])]
            out[i, :, j * n:(j + 1) * n] = b[rng.integers(len(b))]
    return out


def long_chunks(seed, traffic, geometry, n_long, group=None):
    """(n_long, 2, S_long) float32 long chunks and their (n_long, n_seg)
    segment labels for one seed of one traffic mix; each run of `group`
    long chunks (all of them by default) has the mix's exact label
    counts."""
    per_long = int(round(geometry["long_split_s"] / geometry["final_split_s"]))
    probs = [traffic["label_probs"][k] for k in LABELS]
    rng = rng_for(seed, 1)
    bank = _segment_bank(rng, [i for i, p in enumerate(probs) if p > 0],
                         traffic, geometry)
    group = int(group or n_long)
    if n_long % group:
        raise ValueError(f"{n_long} long chunks do not split into groups "
                         f"of {group}")
    labels = np.concatenate([
        label_sequence(rng, probs, traffic["persistence"], group * per_long)
        for _ in range(n_long // group)]).reshape(n_long, per_long)
    return assemble(rng, bank, labels), labels


def graded_batches(traffic, geometry, n_long, kept_counts):
    """One batch of `n_long` long chunks per entry of `kept_counts`, whose
    last `k` 5 s segments are bird song and the rest silence: a batch from
    which detection keeps `k` chunks. Fixed content, whatever the seed."""
    per_long = int(round(geometry["long_split_s"] / geometry["final_split_s"]))
    bird, silence = LABELS.index("bird"), LABELS.index("silence")
    rng = rng_for(0, 5)
    bank = _segment_bank(rng, [bird, silence], traffic, geometry)
    for k in kept_counts:
        labels = np.full(n_long * per_long, silence, np.int32)
        labels[len(labels) - k:] = bird
        yield assemble(rng, bank, labels.reshape(n_long, per_long))
