"""Structural HLO cost walker: trip-count multiplication, dot flops, collectives."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_scan_flops_multiplied_by_trip_count():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.hlo_analysis import analyze_hlo
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

        def f(x, ws):
            def body(h, w):
                return jnp.dot(h, w), None
            h, _ = jax.lax.scan(body, x, ws)
            return h.sum()

        xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        with mesh:
            comp = jax.jit(
                f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                 NamedSharding(mesh, P(None, None, "model")))
            ).lower(xs, ws).compile()
        res = analyze_hlo(comp.as_text())
        # per-device: 5 iters x 2*16*16*64 flops (dot sharded 16x16 @ 64x16)
        print("FLOPS", res["flops"])
        assert res["flops"] == 5 * 2 * 16 * 16 * 64
        assert res["n_dots"] == 5
        assert res["unknown_trip_whiles"] == 0
        # loop-scaled all-gather of the rhs shard
        ag = res["collectives"]["by_kind"].get("all-gather")
        assert ag is not None and ag["count"] == 5
    """)
    assert "FLOPS" in out


def test_parser_handles_synthetic_module():
    from repro.core.hlo_analysis import analyze_hlo
    hlo = """HloModule test, is_scheduled=true

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%i2, %d)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %k = s32[] constant(3)
  ROOT %lt = pred[] compare(%i3, %k), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""
    res = analyze_hlo(hlo)
    assert res["flops"] == 3 * 2 * 8 * 8 * 8
    assert res["n_dots"] == 3
    assert res["unknown_trip_whiles"] == 0


def test_collective_wire_bytes_model():
    from repro.core.hlo_analysis import analyze_hlo
    hlo = """HloModule test, is_scheduled=true

ENTRY %main (a: f32[128]) -> f32[128] {
  %a = f32[128] parameter(0)
  ROOT %ar = f32[128] all-reduce(%a), replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    res = analyze_hlo(hlo)
    ar = res["collectives"]["by_kind"]["all-reduce"]
    assert ar["operand_bytes"] == 512.0
    assert ar["wire_bytes"] == pytest.approx(2 * 3 / 4 * 512.0)
