"""The trip-count-aware HLO analyzer (launch/hlo_count.py): scan == unroll,
fused dots counted, collectives counted through loops (subprocess with forced
device count)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.launch.hlo_count import analyze, parse_hlo

REPO = pathlib.Path(__file__).resolve().parents[1]


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_equals_unroll_flops():
    def scanned(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    def unrolled(x, w):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    a_s = analyze(_compiled_text(scanned, x, w))
    a_u = analyze(_compiled_text(unrolled, x, w))
    assert a_s.flops == a_u.flops == 8 * 2 * 128 ** 3
    # the dominant traffic — 8 weight-slice reads — is counted in both; the
    # scanned form may count slightly less (dynamic-slice reads are charged
    # at slice size; CPU's unrolled form materializes extra copies)
    w_bytes = 8 * 128 * 128 * 4
    assert a_s.bytes >= w_bytes
    assert a_u.bytes >= w_bytes
    assert a_s.bytes <= a_u.bytes * 1.1
    assert a_u.bytes <= 3 * a_s.bytes


def test_nested_scan_multiplies():
    def nested(x, w):
        def outer(c, _):
            def inner(ci, wi):
                return ci @ wi, None
            c, _ = jax.lax.scan(inner, c, w)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    a = analyze(_compiled_text(nested, x, w))
    assert a.flops == 3 * 5 * 2 * 64 ** 3


def test_batched_dot_flops():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)
    a = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
    an = analyze(_compiled_text(f, a, b))
    assert an.flops == 2 * 4 * 32 * 8 * 16


def test_parse_handles_tuple_shapes_and_comments():
    text = textwrap.dedent("""\
    HloModule m
    %body (p: (s32[], f32[4,4], /*index=2*/f32[2,4,4])) -> (s32[], f32[4,4], f32[2,4,4]) {
      %p = (s32[], f32[4,4], f32[2,4,4]) parameter(0)
      %g0 = s32[] get-tuple-element(%p), index=0
      %g1 = f32[4,4]{1,0} get-tuple-element(%p), index=1
      ROOT %t = (s32[], f32[4,4], f32[2,4,4]) tuple(%g0, %g1, %g1)
    }
    ENTRY %main (x: f32[4,4]) -> f32[4,4] {
      %x = f32[4,4]{1,0} parameter(0)
      ROOT %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }
    """)
    comps, entry = parse_hlo(text)
    assert entry == "main"
    assert "body" in comps
    a = analyze(text)
    assert a.flops == 2 * 4 * 4 * 4


def test_collectives_through_scan_subprocess():
    """Needs >1 device: run in a subprocess with forced host device count."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_count import analyze
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        def f(x, w):
            def body(c, wi):
                y = c @ wi
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P(None, None)))
                return y, None
            y, _ = jax.lax.scan(body, x, w)
            return y
        xs = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
        with mesh:
            c = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P(None, "model")),
                NamedSharding(mesh, P(None, None, "model")))).lower(xs, ws).compile()
        a = analyze(c.as_text())
        assert sum(a.coll_bytes.values()) > 0, a.coll_bytes
        assert sum(a.coll_counts.values()) >= 8     # collectives x trip count
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": os.environ.get("HOME", str(REPO))},
                       cwd=str(REPO))
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_bf16_dot_not_inflated():
    """CPU FloatNormalization wraps bf16 dots in f32 converts; the effective-
    width model must count TPU-native bf16 traffic (operands + result at
    2 bytes/elt), not the f32-legalized version."""
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_count import analyze

    def f(x, w):
        return x @ w

    x = jnp.zeros((256, 512), jnp.bfloat16)
    w = jnp.zeros((512, 128), jnp.bfloat16)
    c = jax.jit(f).lower(x, w).compile()
    a = analyze(c.as_text())
    expect = 2 * (256 * 512 + 512 * 128 + 256 * 128)   # bf16 reads + write
    # exact: the only counted op should be the dot at effective width 2
    assert a.bytes == expect, (a.bytes, expect)
    assert a.flops == 2 * 256 * 128 * 512


def test_effective_width_narrows_through_collective():
    """dot(f32 upcast) -> all-reduce -> downcast chain is counted at bf16
    widths end-to-end (the TPU program all-reduces bf16 partials)."""
    from repro.launch.hlo_count import analyze
    text = """
HloModule m

%wc (p: bf16[8,8]) -> f32[8,8] {
  ROOT %convert.1 = f32[8,8]{1,0} convert(%p)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

ENTRY %main (x: bf16[8,8], y: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %y = bf16[8,8]{1,0} parameter(1)
  %cx = f32[8,8]{1,0} fusion(%x), kind=kLoop, calls=%wc
  %cy = f32[8,8]{1,0} fusion(%y), kind=kLoop, calls=%wc
  %d = f32[8,8]{1,0} dot(%cx, %cy), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%sum
  ROOT %out = bf16[8,8]{1,0} convert(%ar)
}
"""
    a = analyze(text)
    # all-reduce counted at bf16 width (8*8*2), not f32
    assert a.coll_bytes["all-reduce"] == 8 * 8 * 2, a.coll_bytes
    # dot: two bf16 reads + one bf16 write + the all-reduce in/out
    assert a.bytes == 3 * (8 * 8 * 2) + 2 * (8 * 8 * 2), a.bytes
