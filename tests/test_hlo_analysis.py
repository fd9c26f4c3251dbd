"""Loop-aware HLO cost analysis: exactness on known programs."""
import os
import subprocess
import sys
import textwrap

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_scan_flops_counted_with_trip_count():
    _run("""
        import jax, jax.numpy as jnp
        from repro import hlo_analysis as ha
        d, T, B = 128, 12, 32
        w = jax.ShapeDtypeStruct((T, d, d), jnp.float32)
        x = jax.ShapeDtypeStruct((B, d), jnp.float32)
        def f(w, x):
            def body(c, wi):
                return jnp.tanh(c @ wi), None
            y, _ = jax.lax.scan(body, x, w)
            return jnp.sum(y)
        c = jax.jit(jax.grad(f)).lower(w, x).compile()
        cost = ha.analyze(c.as_text())
        # fwd T + bwd 2T matmuls of 2*B*d*d flops each
        want = 3 * T * 2 * B * d * d
        assert 0.9 * want <= cost.flops <= 1.3 * want, (cost.flops, want)

        # XLA's own analysis misses the trip count (documents why ours exists)
        xla = c.cost_analysis()
        if isinstance(xla, (list, tuple)): xla = xla[0]
        assert xla["flops"] < 0.5 * want
    """)


def test_collectives_inside_loops_are_scaled():
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import hlo_analysis as ha
        mesh = make_mesh((8,), ("data",))
        T, d = 10, 64
        w = jax.ShapeDtypeStruct((T, d, d), jnp.float32)
        x = jax.ShapeDtypeStruct((32, d), jnp.float32)
        def f(w, x):
            def body(c, wi):
                return jnp.tanh(c @ wi), None
            y, _ = jax.lax.scan(body, x, w)
            return jnp.sum(y)
        g = jax.jit(jax.grad(f),
                    in_shardings=(NamedSharding(mesh, P(None)),
                                  NamedSharding(mesh, P("data"))))
        cost = ha.analyze(g.lower(w, x).compile().as_text())
        ar = cost.coll_counts.get("all-reduce", 0)
        assert ar >= T, f"expected >= {T} loop-scaled all-reduces, got {ar}"
    """)


def test_parse_robust_to_tuple_results_and_comments():
    from repro import hlo_analysis as ha
    text = """\
HloModule m

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4,4] get-tuple-element(%p), index=1
  %y = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4,4]) tuple(%i2, %y)
}

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[4,4]) tuple(%z, %a)
  %w = (s32[], /*index=1*/f32[4,4]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[4,4] get-tuple-element(%w), index=1
}
"""
    cost = ha.analyze(text)
    # 7 trips x dot(4x4,4x4) = 896 MXU flops + 7 loop-counter adds (1 each)
    assert 896 <= cost.flops <= 896 + 8, cost.flops
