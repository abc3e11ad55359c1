"""Validation of the trip-count-aware HLO walker: scan-free graphs must
match an analytic count, and scanned graphs must match their unrolled
equivalents (which XLA's own cost_analysis undercounts)."""
import subprocess
import sys
import textwrap


def _run(body):
    code = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n"
            "import jax, jax.numpy as jnp\n"
            "from repro.launch.hlo_analysis import analyze_hlo\n"
            + textwrap.dedent(body) + "\nprint('SUBPROC_OK')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"}, cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROC_OK" in out.stdout
    return out.stdout


def test_walker_counts_scan_trip_counts():
    _run("""
    L, E, B = 6, 128, 4
    w = jax.ShapeDtypeStruct((L, E, E), jnp.float32)
    x = jax.ShapeDtypeStruct((B, E), jnp.float32)

    def body(h, wl):
        return jnp.tanh(h @ wl), None

    def scanned(ws, h):
        h, _ = jax.lax.scan(body, h, ws)
        return h.sum()

    def unrolled(ws, h):
        for i in range(L):
            h, _ = body(h, ws[i])
        return h.sum()

    fs = analyze_hlo(jax.jit(scanned).lower(w, x).compile().as_text())
    fu = analyze_hlo(jax.jit(unrolled).lower(w, x).compile().as_text())
    expected = 2.0 * B * E * E * L
    assert abs(fs["dot_flops"] - expected) / expected < 0.05, fs
    assert abs(fu["dot_flops"] - expected) / expected < 0.05, fu
    # XLA's own counter misses the trip count on the scanned version
    ca = jax.jit(scanned).lower(w, x).compile().cost_analysis()
    assert ca["flops"] < 0.5 * expected
    """)


def test_walker_counts_collectives_inside_scan():
    _run("""
    from jax.sharding import PartitionSpec as P, NamedSharding
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    L, E, B = 5, 64, 8
    w = jax.ShapeDtypeStruct((L, E, E), jnp.float32)
    x = jax.ShapeDtypeStruct((B, E), jnp.float32)

    def body(h, wl):
        h = h @ wl                      # wl col-sharded -> psum per layer
        h = jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P(None, None)))
        return jnp.tanh(h), None

    def f(ws, h):
        h, _ = jax.lax.scan(body, h, ws)
        return h.sum()

    sh_w = NamedSharding(mesh, P(None, None, "model"))
    c = jax.jit(f, in_shardings=(sh_w, None)).lower(w, x).compile()
    agg = analyze_hlo(c.as_text())
    # at least L reduce/all-gather rounds of the (B,E) activation
    assert agg["coll_bytes"] >= L * B * E * 4 * 0.5, agg
    """)
