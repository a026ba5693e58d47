"""Mesh-native sharded serving tests (ISSUE 7).

Covers:
  * the 1-device degenerate decode mesh is bit-identical to
    ``mesh=None`` — caches, engines, completions;
  * the freed-slot capacity regression: a dead lane's garbage can
    never change a live slot's logits on a capacity-limited MoE mesh
    (and, as a negative control, DOES without the liveness mask);
  * ``sharding/rules.paged_cache_specs`` layouts under the abstract
    16x16 production mesh: pool blocks over "data", feature dims over
    "model", slot-resident state over "data", divisibility always;
  * the per-shard ``PagedAllocator``: contiguous id ownership,
    most-free placement, single-shard ordering unchanged;
  * the ``_overlap_ok`` gate and the ``hlo_analysis`` def-use overlap
    checker on synthetic HLO;
  * (>= 8 devices, e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8)
    per-family sharded-vs-single-device token identity — greedy and
    temperature, contiguous and paged — EP-A2A overlap on/off identity,
    cache sharding persistence across admit/run, and a compiled-HLO
    overlap assertion on the real overlapped decode step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import hlo_analysis as H
from repro.launch.mesh import decode_mesh_shape, make_decode_mesh
from repro.models import model as M
from repro.models import moe
from repro.serve import PagedServeEngine, ServeEngine, Temperature
from repro.serve.paged import PagedAllocator
from repro.sharding import rules

from test_serve_chunked import ENGINE_ARCHS, family_batch, run_engine

MESH16 = AbstractMesh((16, 16), ("data", "model"))

MULTI = len(jax.devices()) >= 8
needs_multi = pytest.mark.skipif(
    not MULTI, reason="needs >= 8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def trivial_mesh():
    return make_decode_mesh(1)


# ---------------------------------------------------------------------------
# 1-device degenerate mesh == mesh=None (bitwise)
# ---------------------------------------------------------------------------

def test_decode_mesh_shapes():
    assert decode_mesh_shape(1) == (1, 1)
    assert decode_mesh_shape(2) == (1, 2)
    assert decode_mesh_shape(4) == (2, 2)
    assert decode_mesh_shape(8) == (2, 4)
    assert decode_mesh_shape(6) == (3, 2)  # odd residue stays on "data"
    assert dict(make_decode_mesh(1).shape) == {"data": 1, "model": 1}


def test_trivial_mesh_cache_init_identical():
    cfg = get_config("qwen2-moe-a2.7b", variant="reduced")
    a = M.init_decode_cache(cfg, 2, 16)
    b = M.init_decode_cache(cfg, 2, 16, mesh=trivial_mesh())
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    pa = M.init_paged_cache(cfg, 2, 8, 4)
    pb = M.init_paged_cache(cfg, 2, 8, 4, mesh=trivial_mesh())
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "mamba2-1.3b"])
def test_trivial_mesh_engine_bit_identical(arch):
    """ServeEngine on the 1-device degenerate decode mesh must emit the
    SAME tokens as mesh=None — same dense MoE path, no placement."""
    cfg = get_config(arch, variant="reduced").replace(overlap_a2a=True)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    lengths = [(6, 4), (9, 6)]
    batches = [family_batch(cfg, p, seed=20 + i)
               for i, (p, _) in enumerate(lengths)]
    max_len = max(M.decode_capacity(cfg, p, g) for p, g in lengths)
    ref, _ = run_engine(ServeEngine, params, cfg, batches, lengths, max_len,
                        n_slots=2, seg_len=3, seed=0, mesh=None)
    mesh = trivial_mesh()
    with mesh:
        got, _ = run_engine(ServeEngine, params, cfg, batches, lengths,
                            max_len, n_slots=2, seg_len=3, seed=0, mesh=mesh)
    assert got == ref


# ---------------------------------------------------------------------------
# freed-slot capacity regression
# ---------------------------------------------------------------------------

def _capacity_rig():
    """A capacity-binding a2a MoE: 16 rows, identity-ish router (feature
    j -> expert j), 12 live rows all preferring expert 0, per-expert
    capacity 8 < 12 so drops are inevitable and rank order matters."""
    cfg = get_config("qwen2-moe-a2.7b", variant="reduced").replace(
        moe_impl="a2a", capacity_factor=0.25, n_shared_experts=0,
        router_aux_coef=0.0)
    E, D = cfg.n_experts, cfg.d_model
    F = cfg.moe_d_ff or cfg.d_ff
    router = np.zeros((D, E), np.float32)
    for e in range(E):
        router[e, e] = 10.0
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "router": jnp.asarray(router),
        "wi_gate": (jax.random.normal(ks[0], (E, D, F)) * 0.1).astype(dt),
        "wi_up": (jax.random.normal(ks[1], (E, D, F)) * 0.1).astype(dt),
        "wo": (jax.random.normal(ks[2], (E, F, D)) * 0.1).astype(dt),
    }
    B = 16
    x = np.zeros((B, 1, D), np.float32)
    x[4:, 0, 0] = 5.0                       # 12 live rows -> expert 0
    x[4:, 0, E:] = (np.arange(12)[:, None] + 1) * 0.01  # distinct outputs
    live = np.ones((B, 1), bool)
    live[:4] = False                        # rows 0..3 are freed slots
    return cfg, p, x, live


def _moe_out(cfg, p, x, garbage_experts, live, mesh):
    """apply_moe with rows 0..3 filled with (finite) garbage whose top-k
    routes to ``garbage_experts`` — (0, 1) contends with the live rows'
    choices, (2, 3) does not."""
    E = cfg.n_experts
    xg = x.copy()
    for ge in garbage_experts:
        xg[:4, 0, ge] = 5.0
    xg[:4, 0, E:] += 100.0                  # wild but finite garbage (the
    # identity router only reads features < E, so the routing preference
    # stays with ``garbage_experts``)
    with mesh:
        out, _ = moe.apply_moe(p, cfg, jnp.asarray(xg, cfg.dtype), mesh=mesh,
                               live=None if live is None
                               else jnp.asarray(live))
    return np.asarray(out)


def test_freed_slot_cannot_steal_capacity():
    """With the liveness mask, a freed slot's garbage routes nowhere: it
    holds no capacity rank and combines with weight 0, so live-slot
    outputs are BITWISE invariant to what the dead lane contains."""
    cfg, p, x, live = _capacity_rig()
    mesh = trivial_mesh()
    a = _moe_out(cfg, p, x, garbage_experts=(0, 1), live=live, mesh=mesh)
    b = _moe_out(cfg, p, x, garbage_experts=(2, 3), live=live, mesh=mesh)
    np.testing.assert_array_equal(a[4:], b[4:])
    assert np.all(np.isfinite(a))
    # dead rows combine with weight zero: their MoE output is exactly 0
    np.testing.assert_array_equal(a[:4], np.zeros_like(a[:4]))


def test_freed_slot_steals_capacity_without_mask():
    """Negative control: live=None (the pre-mask behavior) lets garbage
    rows occupy expert-0 capacity ranks ahead of live rows, changing
    which live assignments are dropped — live outputs diverge."""
    cfg, p, x, _ = _capacity_rig()
    mesh = trivial_mesh()
    a = _moe_out(cfg, p, x, garbage_experts=(0, 1), live=None, mesh=mesh)
    b = _moe_out(cfg, p, x, garbage_experts=(2, 3), live=None, mesh=mesh)
    assert np.any(a[4:] != b[4:])


# ---------------------------------------------------------------------------
# paged-pool sharding specs (abstract 16x16 production mesh)
# ---------------------------------------------------------------------------

def _paged_layout(arch, n_slots, n_blocks, block_len):
    cfg = get_config(arch, variant="reduced")
    cache = jax.eval_shape(
        lambda: M.init_paged_cache(cfg, n_slots, n_blocks, block_len))
    bax = M.decode_cache_batch_axes(cfg)
    sax = M.decode_cache_seq_axes(cfg)
    specs = rules.paged_cache_specs(cache, MESH16, batch_axes=bax,
                                    seq_axes=sax)
    flat = list(zip(jax.tree.leaves(cache),
                    jax.tree.leaves(specs,
                                    is_leaf=lambda s: isinstance(s, P)),
                    jax.tree.leaves(bax), jax.tree.leaves(sax)))
    return cfg, flat


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "deepseek-v3-671b",
                                  "mamba2-1.3b", "whisper-small"])
def test_paged_cache_specs_layouts(arch):
    n_data = MESH16.shape["data"]
    model = MESH16.shape["model"]
    cfg, flat = _paged_layout(arch, n_slots=16, n_blocks=64, block_len=8)
    saw_model = False
    for leaf, spec, bax, sax in flat:
        # pool/slot dim over "data" whenever divisible (n_blocks=64,
        # n_slots=16 both divide the 16-way data axis)
        if leaf.shape[bax] % n_data == 0:
            assert spec[bax] == "data", (leaf.shape, spec, bax)
        # pool leaves: trailing feature dim on "model" exactly when the
        # rule allows it; slot-resident leaves never shard on "model"
        last = leaf.ndim - 1
        if sax >= 0:
            expect = (last != bax and spec[last] != "data"
                      and leaf.shape[last] % model == 0
                      and leaf.shape[last] >= model)
            assert (spec[last] == "model") == expect, (leaf.shape, spec)
            saw_model |= spec[last] == "model"
        else:
            assert "model" not in tuple(spec), (leaf.shape, spec)
        # divisibility invariant: every assigned axis divides exactly
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= MESH16.shape[a]
            assert leaf.shape[dim] % n == 0, (leaf.shape, spec, dim)
    if arch in ("qwen2-moe-a2.7b", "deepseek-v3-671b"):
        assert saw_model  # KV heads x head_dim / MLA latent width shards


def test_paged_cache_specs_non_divisible_replicates():
    """A pool that doesn't divide the data axis replicates (never an
    error) — the engine likewise falls back to n_shards=1."""
    _, flat = _paged_layout("tinyllama-1.1b", n_slots=3, n_blocks=18,
                            block_len=4)
    for leaf, spec, bax, sax in flat:
        if leaf.shape[bax] in (3, 18):
            assert spec[bax] is None, (leaf.shape, spec)


# ---------------------------------------------------------------------------
# per-shard allocator
# ---------------------------------------------------------------------------

def test_allocator_shards_own_contiguous_ranges():
    al = PagedAllocator(8, 4, n_shards=2)
    assert [al.shard_of(b) for b in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]
    # trash block 0 lives in shard 0 and is never free
    assert 0 not in al.free_ids()
    assert al.n_free_shard(0) == 3 and al.n_free_shard(1) == 4
    assert al.n_free == 7 and al.n_live == 0


def test_allocator_balances_across_shards():
    al = PagedAllocator(8, 4, n_shards=2)
    # shard 1 has one more free block (no trash): first alloc comes from
    # it; ties then break to the lowest shard index
    seq = [al.alloc() for _ in range(7)]
    assert [al.shard_of(b) for b in seq] == [1, 0, 1, 0, 1, 0, 1]
    assert seq == [4, 1, 5, 2, 6, 3, 7]  # low ids first within a shard
    assert al.n_free == 0
    with pytest.raises(RuntimeError):
        al.alloc()
    al.release(6)
    assert al.n_free_shard(1) == 1 and al.n_free_shard(0) == 0
    assert al.shard_of(al.alloc()) == 1


def test_allocator_single_shard_order_unchanged():
    """n_shards=1 must hand out the exact id sequence of the pre-shard
    allocator: ascending ids, LIFO recycle."""
    al = PagedAllocator(6, 4)
    assert al.n_shards == 1
    assert [al.alloc() for _ in range(3)] == [1, 2, 3]
    al.release(2)
    assert al.alloc() == 2
    assert al.alloc() == 4


def test_allocator_rejects_bad_shard_split():
    with pytest.raises(ValueError):
        PagedAllocator(10, 4, n_shards=4)


def test_engine_trivial_mesh_keeps_single_shard_allocator():
    """n_data=1 meshes must not split the allocator (id order — and so
    block placement — stays identical to mesh=None)."""
    cfg = get_config("tinyllama-1.1b", variant="reduced")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    eng = PagedServeEngine(params, cfg, n_slots=2, max_len=16,
                           mesh=trivial_mesh(), block_len=4, n_blocks=8)
    assert eng.alloc.n_shards == 1


# ---------------------------------------------------------------------------
# overlap gate + HLO def-use checker
# ---------------------------------------------------------------------------

def test_overlap_ok_gate():
    moe_cfg = get_config("qwen2-moe-a2.7b",
                         variant="reduced").replace(overlap_a2a=True)
    dense_cfg = get_config("tinyllama-1.1b",
                           variant="reduced").replace(overlap_a2a=True)
    mesh = AbstractMesh((2, 4), ("data", "model"))
    flat = AbstractMesh((1, 8), ("data", "model"))
    one = AbstractMesh((8, 1), ("data", "model"))
    assert M._overlap_ok(moe_cfg, mesh, 4, None)
    assert M._overlap_ok(moe_cfg, flat, 2, None)
    assert not M._overlap_ok(moe_cfg.replace(overlap_a2a=False), mesh, 4, None)
    assert not M._overlap_ok(dense_cfg, mesh, 4, None)          # not MoE
    assert not M._overlap_ok(moe_cfg, None, 4, None)            # no mesh
    assert not M._overlap_ok(moe_cfg, one, 4, None)             # model == 1
    assert not M._overlap_ok(moe_cfg, mesh, 3, None)            # odd batch
    assert not M._overlap_ok(moe_cfg, mesh, 0, None)            # empty
    assert not M._overlap_ok(moe_cfg, mesh, 4, object())        # paged
    assert not M._overlap_ok(moe_cfg.replace(moe_impl="replicated_ep"),
                             mesh, 4, None)


_HLO_INDEPENDENT = """
HloModule m

%ffn (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  ROOT %d = f32[8,8] dot(%p0, %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %b = f32[8,8] parameter(1)
  %a2a = f32[8,8] all-to-all(%a), replica_groups={{0,1}}
  %mm = f32[8,8] fusion(%b), kind=kLoop, calls=%ffn
  ROOT %r = f32[8,8] add(%a2a, %mm)
}
"""

_HLO_DEPENDENT = """
HloModule m

ENTRY %main (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %b = f32[8,8] parameter(1)
  %a2a = f32[8,8] all-to-all(%a), replica_groups={{0,1}}
  ROOT %mm = f32[8,8] dot(%a2a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

_HLO_NO_A2A = """
HloModule m

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  ROOT %mm = f32[8,8] dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_hlo_overlap_independent_fusion_dot():
    pairs = H.a2a_overlap_pairs(_HLO_INDEPENDENT)
    assert [(c, a) for c, a, _ in pairs] == [("main", "a2a")]
    assert pairs[0][2] >= 1  # the %mm fusion (dot-bearing) is independent
    H.assert_a2a_overlap(_HLO_INDEPENDENT)


def test_hlo_overlap_dependent_dot_raises():
    pairs = H.a2a_overlap_pairs(_HLO_DEPENDENT)
    assert pairs == [("main", "a2a", 0)]  # the only dot consumes the a2a
    with pytest.raises(AssertionError):
        H.assert_a2a_overlap(_HLO_DEPENDENT)


def test_hlo_overlap_no_a2a_raises():
    with pytest.raises(AssertionError):
        H.assert_a2a_overlap(_HLO_NO_A2A)


# ---------------------------------------------------------------------------
# multi-device: sharded-vs-single token identity, overlap, placement
# ---------------------------------------------------------------------------

def _traffic(cfg, n=4):
    lengths = [(6, 4), (9, 6), (7, 5), (11, 3)][:n]
    batches = [family_batch(cfg, p, seed=10 + i)
               for i, (p, _) in enumerate(lengths)]
    max_len = max(M.decode_capacity(cfg, p, g) for p, g in lengths)
    return batches, lengths, max_len


@needs_multi
@pytest.mark.parametrize("arch", ENGINE_ARCHS)
def test_sharded_engine_matches_single_device(arch):
    """The decode-mesh engine must emit token-identical completions to
    the single-device engine on every arch family (greedy)."""
    cfg = get_config(arch, variant="reduced")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    batches, lengths, max_len = _traffic(cfg)
    ref, _ = run_engine(ServeEngine, params, cfg, batches, lengths, max_len,
                        n_slots=2, seg_len=3, seed=0, mesh=None)
    mesh = make_decode_mesh()
    assert mesh.shape["model"] > 1
    with mesh:
        got, eng = run_engine(ServeEngine, params, cfg, batches, lengths,
                              max_len, n_slots=2, seg_len=3, seed=0,
                              mesh=mesh)
    assert got == ref
    # the cache layout survives admission grafts and the decode scan
    assert any(not l.sharding.is_fully_replicated
               for l in jax.tree.leaves(eng.cache))


@needs_multi
@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "tinyllama-1.1b"])
def test_sharded_paged_engine_matches_single_device(arch):
    cfg = get_config(arch, variant="reduced")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    batches, lengths, max_len = _traffic(cfg)
    kw = dict(n_slots=2, seg_len=3, seed=0, block_len=4, n_blocks=32)
    ref, _ = run_engine(PagedServeEngine, params, cfg, batches, lengths,
                        max_len, mesh=None, **kw)
    mesh = make_decode_mesh()
    with mesh:
        got, eng = run_engine(PagedServeEngine, params, cfg, batches,
                              lengths, max_len, mesh=mesh, **kw)
    assert got == ref
    # 32 blocks / data axis -> per-shard free lists engaged
    assert eng.alloc.n_shards == mesh.shape["data"]
    assert eng.alloc.n_free == eng.alloc.n_blocks - 1  # drained


@needs_multi
def test_sharded_sampling_matches_single_device():
    """Temperature sampling: the per-request key protocol is mesh-blind,
    so stochastic completions match too."""
    cfg = get_config("qwen2-moe-a2.7b", variant="reduced")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    batches, lengths, max_len = _traffic(cfg, n=3)
    kw = dict(n_slots=2, seg_len=3, seed=7, sampler=Temperature(0.8))
    ref, _ = run_engine(ServeEngine, params, cfg, batches, lengths, max_len,
                        mesh=None, **kw)
    mesh = make_decode_mesh()
    with mesh:
        got, _ = run_engine(ServeEngine, params, cfg, batches, lengths,
                            max_len, mesh=mesh, **kw)
    assert got == ref


@needs_multi
def test_overlap_a2a_token_identity():
    """cfg.overlap_a2a splits the decode batch in half around the EP
    all-to-all; at serving capacity (no drops) completions must be
    token-identical with the overlap off."""
    cfg = get_config("qwen2-moe-a2.7b", variant="reduced")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    batches, lengths, max_len = _traffic(cfg)
    mesh = make_decode_mesh()
    with mesh:
        off, _ = run_engine(ServeEngine, params, cfg, batches, lengths,
                            max_len, n_slots=2, seg_len=3, seed=0, mesh=mesh)
        on, _ = run_engine(ServeEngine, params,
                           cfg.replace(overlap_a2a=True), batches, lengths,
                           max_len, n_slots=2, seg_len=3, seed=0, mesh=mesh)
    assert on == off


@needs_multi
def test_overlapped_decode_step_hlo_has_independent_a2a():
    """Compile the overlapped decode step on the real decode mesh and
    assert, at the HLO level, that an all-to-all has dataflow-independent
    matmul work to hide behind (the other half's attention/FFN)."""
    cfg = get_config("qwen2-moe-a2.7b",
                     variant="reduced").replace(overlap_a2a=True)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    mesh = make_decode_mesh()
    B = 2
    with mesh:
        cache = M.init_decode_cache(cfg, B, 16, mesh=mesh)
        toks = jnp.zeros((B, 1), jnp.int32)
        pos = jnp.asarray([3, 5], jnp.int32)
        live = jnp.ones((B,), jnp.bool_)
        assert M._overlap_ok(cfg, mesh, B, None)
        fn = jax.jit(lambda p, c, t, q, lv: M.decode_step(
            p, cfg, c, t, q, mesh=mesh, live=lv))
        txt = fn.lower(params, cache, toks, pos, live).compile().as_text()
    H.assert_a2a_overlap(txt)
