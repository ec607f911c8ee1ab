"""Distribution layer: shape-aware sharding resolution, HLO collective
parsing, and a real (host-sized) mesh lowering with constraints applied."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.hlo_analysis import (ICI_BW, PEAK_FLOPS, collective_bytes,
                                            roofline_terms)
from repro.distributed.sharding import Resolver
from repro.launch.mesh import make_host_mesh


def _resolver(arch="granite-20b"):
    cfg = get_config(arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    r = Resolver(cfg, mesh)
    r.sizes = {"data": 16, "model": 16}  # pretend production sizes
    return r


def test_resolver_divisibility_drops_axis():
    r = _resolver()
    # 48 heads % 16 == 0 → sharded; kv=1 → replicated
    assert r.spec(("embed", "heads", "head"), (6144, 48, 128)) == P("data", "model", None)
    assert r.spec(("embed", "kv_heads", "head"), (6144, 1, 128)) == P("data", None, None)
    # llama3.2: 24 heads % 16 != 0 → dropped
    assert r.spec(("embed", "heads", "head"), (3072, 24, 128)) == P("data", None, None)


def test_resolver_batch_axes_multi_pod():
    cfg = get_config("yi-9b")
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    r = Resolver(cfg, mesh)
    r.sizes = {"pod": 2, "data": 16, "model": 16}
    assert r.spec(("batch", None), (256, 4096)) == P(("pod", "data"), None)
    # batch=1 (long_500k): nothing fits → fully replicated
    assert r.spec(("batch", None), (1, 4096)) == P(None, None)


def test_resolver_never_reuses_mesh_axis():
    r = _resolver()
    spec = r.spec(("vocab", "ffn"), (49152, 24576))
    flat = [a for s in spec if s for a in (s if isinstance(s, tuple) else (s,))]
    assert len(flat) == len(set(flat))


HLO_SAMPLE = """
HloModule test
%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}
ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256] parameter(0)
  %ag = f32[256,256]{1,0} all-gather(f32[128,256]{1,0} %p0), dimensions={0}
  %ar = f32[256,256]{1,0} all-reduce(f32[256,256]{1,0} %ag), to_apply=%add
  %ard = f32[256,256]{1,0} all-reduce-done(f32[256,256]{1,0} %ar)
  ROOT %rs = f32[128,256]{1,0} reduce-scatter(f32[256,256]{1,0} %ard), dimensions={0}
}
"""


def test_collective_bytes_parser():
    out = collective_bytes(HLO_SAMPLE)
    assert out["count_all-gather"] == 1
    assert out["count_all-reduce"] == 1        # -done must NOT double count
    assert out["count_reduce-scatter"] == 1
    assert out["bytes_all-gather"] == 256 * 256 * 4
    assert out["bytes_all-reduce"] == 2 * 256 * 256 * 4  # ring factor 2
    assert out["bytes_total"] > 0


def test_roofline_terms_units():
    cost = {"flops": PEAK_FLOPS, "bytes accessed": 0.0}
    terms = roofline_terms(cost, {"bytes_total": ICI_BW}, 256)
    assert terms["t_compute"] == pytest.approx(1.0)
    assert terms["t_collective"] == pytest.approx(1.0)


def test_host_mesh_lowering_with_constraints():
    """End-to-end: resolver-constrained train step lowers + compiles on the
    host mesh (1 device) — the same path the 512-device dry-run takes."""
    from repro.launch.dryrun import dryrun_cell  # noqa: F401  (import sanity)
    from repro.models import Model, unbox
    from repro.models.layers import (reset_activation_resolver,
                                     set_activation_resolver)

    cfg = get_config("yi-9b", smoke=True)
    mesh = make_host_mesh()
    resolver = Resolver(cfg, mesh)
    model = Model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    tok = set_activation_resolver(resolver)
    try:
        with mesh:
            batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                     "targets": jnp.zeros((2, 16), jnp.int32)}
            loss, _ = jax.jit(model.loss)(params, batch)
        assert jnp.isfinite(loss)
    finally:
        reset_activation_resolver(tok)
