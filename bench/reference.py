"""The plain reference: the SERF stage chain in float32 `jax.numpy`.

It follows the paper's order (Brown et al., arXiv:1802.00535) as the
configuration states it, and imports nothing of the program:

  mono mix -> band-pass FIR (1 kHz .. target Nyquist, windowed sinc,
  Hamming) with 2:1 decimation -> 15 s chunks -> Hamming STFT (256, hop
  128) -> acoustic indices -> rain rule, cicada rule and band-stop ->
  overlap-add resynthesis -> 5 s chunks -> envelope-SNR silence rule on the
  15 s power regrouped per 5 s -> keep = ~rain & ~silence -> MMSE-STSA
  (decision-directed, Ephraim & Malah) of the kept chunks.

Every DFT, inverse DFT and the FIR are dot products. At `precision`
"highest" they run in float32 (`Precision.HIGHEST`): that is the
reference. At "bf16_3x" each operand is split into a high and a low
bfloat16 part and the three larger partial products are summed, the
arithmetic of `Precision.HIGH`, written out so that it is the same on every
backend: that is the control. The reference runs in fixed blocks of long
chunks and of rows, so it compiles once per block shape and its memory
stays small.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EPS = 1e-10
XI_MIN = 10.0 ** (-25.0 / 10.0)
GAMMA_MAX = 10.0 ** (40.0 / 10.0)
PRECISIONS = ("highest", "bf16_3x")
HIGHEST = lax.Precision.HIGHEST


def hamming(n):
    return 0.54 - 0.46 * np.cos(2.0 * np.pi * np.arange(n) / (n - 1))


def _lowpass(cutoff_norm, n_taps):
    m = np.arange(n_taps) - (n_taps - 1) / 2.0
    h = 2.0 * cutoff_norm * np.sinc(2.0 * cutoff_norm * m) * hamming(n_taps)
    return h / h.sum()


def bandpass_taps(g):
    rate = g["source_rate_hz"]
    return (_lowpass(g["target_rate_hz"] / 2.0 / rate, g["hpf_taps"])
            - _lowpass(g["hpf_cutoff_hz"] / rate, g["hpf_taps"]))


def _split(x):
    """float32 x as hi + lo, two bfloat16 arrays: hi is x rounded to the
    nearest bfloat16 (ties to even), lo the rest rounded. hi is rounded on
    the bits, since a compiler allowed excess precision may drop a float32
    -> bfloat16 -> float32 round trip and leave lo at 0."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
    hi = lax.bitcast_convert_type(bits & np.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def n_frames(n, window, hop):
    return (n - window) // hop + 1


class Reference:
    """`detect(long_chunks)` and `tail(rows)` of one configuration's
    geometry (the `geometry` object of a `bench/configs` file)."""

    def __init__(self, geometry, precision="highest", block_long=4,
                 block_rows=64):
        g = self.g = dict(geometry)
        if 2 * g["stft_hop"] != g["stft_window"]:
            raise ValueError("the reference implements 50% overlap only")
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.three_pass = precision == "bf16_3x"
        self.block_long = block_long
        self.block_rows = block_rows
        self.rate = g["target_rate_hz"]
        self.win = g["stft_window"]
        self.hop = g["stft_hop"]
        self.K = self.win // 2 + 1
        self.n_detect = int(round(g["long_split_s"] / g["detect_split_s"]))
        self.n_final = int(round(g["detect_split_s"] / g["final_split_s"]))
        self.s15 = int(g["detect_split_s"] * self.rate)
        self.s5 = int(g["final_split_s"] * self.rate)
        n = np.arange(self.win)[:, None]
        k = np.arange(self.K)[None, :]
        ang = 2.0 * np.pi * n * k / self.win
        self.fwd = np.concatenate([np.cos(ang), -np.sin(ang)], 1).astype(
            np.float32)                                   # (N, 2K)
        a = np.full(self.K, 2.0 / self.win)
        a[0] = a[-1] = 1.0 / self.win
        self.inv = np.concatenate([a[:, None] * np.cos(ang.T),
                                   -a[:, None] * np.sin(ang.T)], 0).astype(
            np.float32)                                   # (2K, N)
        self.window = hamming(self.win).astype(np.float32)
        self.freqs = np.arange(self.K) * self.rate / self.win
        self._detect = jax.jit(self._detect_block)
        self._tail = jax.jit(self._tail_block)

    # -- building blocks ----------------------------------------------------
    def _passes(self, op, a, b):
        """op(a, b) for a bilinear op, in float32 or in three bf16 passes."""
        if not self.three_pass:
            return op(a, b)
        (ah, al), (bh, bl) = _split(a), _split(b)
        return op(ah, bh) + (op(ah, bl) + op(al, bh))

    def _dot(self, a, b):
        return self._passes(lambda x, y: jnp.matmul(
            x, y, precision=HIGHEST, preferred_element_type=jnp.float32),
            a, b)

    def _frames(self, x):
        """(B, S) -> (B, F, win): frame f starts at f * hop."""
        B, S = x.shape
        F = n_frames(S, self.win, self.hop)
        ne, no = (F + 1) // 2, F // 2
        even = x[:, :ne * self.win].reshape(B, ne, self.win)
        odd = x[:, self.hop:self.hop + no * self.win].reshape(B, no,
                                                              self.win)
        if no < ne:
            odd = jnp.concatenate([odd, jnp.zeros((B, 1, self.win))], 1)
        return jnp.stack([even, odd], 2).reshape(B, 2 * ne, self.win)[:, :F]

    def _stft(self, x):
        z = self._dot(self._frames(x) * self.window, self.fwd)
        return z[..., :self.K], z[..., self.K:]

    def _istft(self, re, im, n):
        """Windowed overlap-add of the inverse DFT, normalised by the
        summed squared window; samples no frame covers are 0."""
        fr = self._dot(jnp.concatenate([re, im], -1), self.inv) * self.window
        B, F, W = fr.shape
        ne, no = (F + 1) // 2, F // 2
        L = ne * W + self.hop
        out = jnp.zeros((B, L)).at[:, :ne * W].set(
            fr[:, 0::2].reshape(B, -1))
        out = out.at[:, self.hop:self.hop + no * W].add(
            fr[:, 1::2].reshape(B, -1))
        w2 = self.window.astype(np.float64) ** 2
        norm = np.zeros(L)
        norm[:ne * W] += np.tile(w2, ne)
        norm[self.hop:self.hop + no * W] += np.tile(w2, no)
        out = jnp.where(norm > 0, out / np.maximum(norm, 1e-30), 0.0)
        return jnp.pad(out[:, :n], ((0, 0), (0, max(0, n - L))))

    def _band(self, lo, hi):
        return ((self.freqs >= lo) & (self.freqs <= hi)).astype(np.float32)

    @staticmethod
    def snr(power):
        env = power.sum(-1)
        return jnp.clip(1.0 - env.mean(1) / (env.max(1) + EPS), 0.0, 1.0)

    # -- detection: long chunks -> wave5 and masks --------------------------
    def _detect_block(self, x):
        g = self.g
        L = x.shape[0]
        mono = x.mean(1)
        taps = bandpass_taps(g)
        xp = jnp.pad(mono, ((0, 0), (len(taps) - 1, 0)))
        dec = g["source_rate_hz"] // g["target_rate_hz"]
        y = self._passes(lambda a, b: lax.conv_general_dilated(
            a, b, (dec,), "VALID", dimension_numbers=("NCH", "OIH", "NCH"),
            precision=HIGHEST, preferred_element_type=jnp.float32),
            xp[:, None, :],
            jnp.asarray(taps[::-1].copy(), jnp.float32)[None, None, :])[
            :, 0, :mono.shape[1] // dec]
        d = y.reshape(L * self.n_detect, self.s15)
        re, im = self._stft(d)
        power = re * re + im * im                      # (B, F, K)
        psd = jnp.log1p(power.mean((1, 2)))
        snr = self.snr(power)
        p = power + EPS
        flat = (jnp.exp(jnp.log(p).mean(-1)) / p.mean(-1)).mean(1)
        total = power.sum((1, 2)) + EPS
        cb = self._band(*g["cicada_band_hz"])
        cicada_band = (power * cb).sum((1, 2)) / total
        mean_psd = power.mean(1)                       # (B, K)
        sel = np.flatnonzero(cb)
        band_psd = mean_psd[:, sel]
        peakiness = band_psd.max(1) / (jnp.median(mean_psd, 1) + EPS)
        peak_bin = jnp.argmax(band_psd, 1) + int(sel[0])
        persist = (((power * cb).sum(-1) / (power.sum(-1) + EPS)) > 0.5
                   ).mean(1)
        rain = ((psd > g["rain_psd_min"]) & (flat > g["rain_flatness_min"])
                & (snr < g["rain_snr_max"]))
        cicada = ((peakiness > g["cicada_peakiness_min"])
                  & (cicada_band > g["cicada_band_ratio_min"])
                  & (persist > g["cicada_persistence_min"]) & ~rain)
        half = int(round(g["cicada_stop_width_hz"]
                         / (self.rate / self.win))) // 2
        stop = (jnp.abs(jnp.arange(self.K)[None, :] - peak_bin[:, None])
                <= half) & cicada[:, None]
        re = jnp.where(stop[:, None, :], 0.0, re)
        im = jnp.where(stop[:, None, :], 0.0, im)
        wave15 = self._istft(re, im, self.s15)
        wave5 = wave15.reshape(-1, self.s5)
        F, Fg = power.shape[1], n_frames(self.s5, self.win, self.hop)
        starts = [min(int(round(i * self.s5 / self.hop)), F - Fg)
                  for i in range(self.n_final)]
        grouped = jnp.stack([power[:, s:s + Fg] for s in starts], 1)
        rain5 = jnp.repeat(rain, self.n_final)
        silence = (self.snr(grouped.reshape(-1, Fg, self.K))
                   < g["silence_snr_threshold"]) & ~rain5
        return {"wave5": wave5, "keep": ~rain5 & ~silence, "rain": rain5,
                "silence": silence, "cicada15": cicada}

    # -- the survivor tail: MMSE-STSA ---------------------------------------
    @staticmethod
    def _gain(v, gamma):
        v = jnp.maximum(v, 1e-8)
        g = (np.sqrt(np.pi) / 2 * jnp.sqrt(v) / gamma
             * ((1.0 + v) * jax.scipy.special.i0e(v / 2.0)
                + v * jax.scipy.special.i1e(v / 2.0)))
        return jnp.clip(g, 0.0, 10.0)

    def _tail_block(self, rows):
        g = self.g
        re, im = self._stft(rows)
        power = re * re + im * im
        lam = jnp.maximum(power[:, :g["noise_est_frames"]].mean(1), 1e-10)
        gamma = jnp.clip(power / lam[:, None, :], 1e-8, GAMMA_MAX)
        alpha = g["mmse_alpha"]

        def step(a2, gamma_t):
            xi = jnp.maximum(alpha * a2 + (1.0 - alpha)
                             * jnp.maximum(gamma_t - 1.0, 0.0), XI_MIN)
            gain = self._gain(xi * gamma_t / (1.0 + xi), gamma_t)
            return gain * gain * gamma_t, jnp.maximum(gain,
                                                      g["mmse_gain_floor"])

        _, gains = lax.scan(step, jnp.ones_like(gamma[:, 0]),
                            jnp.moveaxis(gamma, 1, 0))
        gains = jnp.moveaxis(gains, 0, 1)
        return self._istft(re * gains, im * gains, rows.shape[1])

    # -- whole batches, in blocks -------------------------------------------
    def detect(self, long_chunks):
        """(N, C, S_long) host array -> dict of host arrays (N * per_long
        rows of wave5 and masks, N * n_detect cicada flags)."""
        x = np.asarray(long_chunks, np.float32)
        n, b = x.shape[0], self.block_long
        pad = (-n) % b
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                            np.float32)])
        parts = [jax.device_get(self._detect(jnp.asarray(x[i:i + b])))
                 for i in range(0, x.shape[0], b)]
        per5 = self.n_detect * self.n_final
        return {k: np.concatenate([p[k] for p in parts])[
            :n * (self.n_detect if k == "cicada15" else per5)]
            for k in parts[0]}

    def tail(self, rows):
        """(R, S5) host rows -> cleaned (R, S5) host array."""
        x = np.asarray(rows, np.float32)
        n, b = x.shape[0], self.block_rows
        if n == 0:
            return np.zeros((0, self.s5), np.float32)
        x = np.concatenate([x, np.zeros(((-n) % b, x.shape[1]), np.float32)])
        return np.concatenate([np.asarray(self._tail(jnp.asarray(x[i:i + b])))
                               for i in range(0, x.shape[0], b)])[:n]

    def run(self, long_chunks):
        """Detection, then the tail of the kept rows: masks, wave5 and the
        cleaned rows in keep order."""
        det = self.detect(long_chunks)
        det["cleaned"] = self.tail(det["wave5"][det["keep"]])
        return det

