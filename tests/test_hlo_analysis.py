"""hlo_analysis unit tests on synthetic HLO text + a real lowered program."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze_collectives

SYNTH = """
HloModule test

%body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add.2
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[128])) -> pred[] {
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main.1 (a: f32[128]) -> f32[128] {
  %ag = f32[256]{0} all-gather(%a), replica_groups=[2,4]<=[8], dimensions={0}
  %w = (s32[], f32[128]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[128] get-tuple-element(%w), index=1
}
"""


def test_synthetic_hlo_trip_count_multiplication():
    res = analyze_collectives(SYNTH)
    ar = res["by_kind"]["all-reduce"]
    # 7 iterations x 128 f32 = 7 * 512B operands
    assert ar["count"] == 7.0
    assert ar["operand_bytes"] == 7 * 512
    # ring wire: 2 * 512 * 3/4 * 7
    assert abs(ar["wire_bytes"] - 2 * 512 * 0.75 * 7) < 1e-6
    ag = res["by_kind"]["all-gather"]
    assert ag["count"] == 1.0
    assert ag["result_bytes"] == 1024.0           # f32[256]
    assert abs(ag["wire_bytes"] - 1024 * 0.75) < 1e-6   # groups of 4


def test_real_scan_program_counts_iterations():
    def scanned(x, w):
        def body(c, _):
            return jax.lax.psum(c @ w, "i"), None
        return jax.lax.scan(body, x, None, length=5)[0]

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np
    mesh = Mesh(np.array(jax.devices()[:1]), ("i",))
    f = shard_map(scanned, mesh=mesh, in_specs=(P(), P()), out_specs=P())
    hlo = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((8, 8))) \
        .compile().as_text()
    res = analyze_collectives(hlo)
    if res["by_kind"]:  # single-device psum may be optimized away
        ar = res["by_kind"].get("all-reduce")
        if ar:
            assert ar["count"] == 5.0
