import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.attention import mha
from kubeflow_tpu.ops.flash_attention import flash_attention
from kubeflow_tpu.ops.ring_attention import ring_attention_sharded
from kubeflow_tpu.ops.ulysses import ulysses_attention_sharded
from kubeflow_tpu.parallel import MeshConfig, make_mesh


def make_qkv(b=2, s=64, h=4, hkv=2, d=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_mha(causal):
    q, k, v = make_qkv()
    ref = mha(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_kv=16, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_and_offset():
    # decode-style: 1 query at position 37 against 64 keys
    q, k, v = make_qkv(s=64)
    q1 = q[:, 37:38]
    ref = mha(q1, k, v, causal=True, q_offset=37)
    out = flash_attention(q1, k, v, causal=True, q_offset=37, block_kv=16,
                          impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_grad_matches_mha():
    q, k, v = make_qkv(s=32)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_kv=8,
                                       impl="xla") ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_segment_ids_match_mha(causal):
    # packed batch: two documents per row; no cross-document attention
    q, k, v = make_qkv(s=64)
    seg = jnp.concatenate(
        [jnp.zeros((2, 24), jnp.int32), jnp.ones((2, 40), jnp.int32)], axis=1)
    ref = mha(q, k, v, causal=causal, segment_ids=seg)
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                          block_kv=16, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_mha(devices8, causal):
    mesh = make_mesh(MeshConfig(sequence=8), devices=devices8)
    q, k, v = make_qkv(b=2, s=64, h=4, hkv=4, d=16)
    ref = mha(q, k, v, causal=causal)
    out = jax.jit(lambda a, b, c: ring_attention_sharded(
        a, b, c, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_gqa(devices8):
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=32, h=4, hkv=2, d=8)
    ref = mha(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_mha(devices8, causal):
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=2, s=64, h=4, hkv=4, d=16)
    ref = mha(q, k, v, causal=causal)
    out = jax.jit(lambda a, b, c: ulysses_attention_sharded(
        a, b, c, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_uneven_kv(devices8):
    # hkv=2 does not divide the 4-way seq axis -> full-head expansion path
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=32, h=4, hkv=2, d=8)
    ref = mha(q, k, v, causal=True)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_segment_ids(devices8):
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=2, s=64, h=4, hkv=4, d=16)
    seg = jnp.concatenate(
        [jnp.zeros((2, 24), jnp.int32), jnp.ones((2, 40), jnp.int32)], axis=1)
    ref = mha(q, k, v, causal=True, segment_ids=seg)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=True,
                                    segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_grad_matches_mha(devices8):
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=32, h=4, hkv=4, d=8)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    def loss_uly(q, k, v):
        return jnp.sum(ulysses_attention_sharded(q, k, v, mesh,
                                                 causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_uly):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode on CPU — same numerics as compiled Mosaic)
# ---------------------------------------------------------------------------

@pytest.fixture()
def pallas_interpret(monkeypatch):
    from kubeflow_tpu.ops import flash_pallas
    monkeypatch.setattr(flash_pallas, "FORCE_INTERPRET", True)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_matches_mha(pallas_interpret, causal):
    q, k, v = make_qkv(b=1, s=256, h=2, hkv=2, d=32, seed=3)
    ref = mha(q, k, v, causal=causal)
    from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention
    out = pallas_flash_attention(q, k, v, causal=causal,
                                 block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pallas_flash_unpadded_seq(pallas_interpret):
    # 200 is not a multiple of 128 — exercises key masking + query padding
    q, k, v = make_qkv(b=1, s=200, h=2, hkv=2, d=32, seed=4)
    ref = mha(q, k, v, causal=True)
    from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention
    out = pallas_flash_attention(q, k, v, causal=True,
                                 block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pallas_flash_grad_matches_mha(pallas_interpret):
    q, k, v = make_qkv(b=1, s=256, h=2, hkv=2, d=32, seed=5)
    from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    def loss_pallas(q, k, v):
        return jnp.sum(pallas_flash_attention(
            q, k, v, causal=True, block_q=128, block_kv=128) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_segment_ids(pallas_interpret, causal):
    # packed batch stays on the kernel path (VERDICT r1 #5): two documents
    # per row with the boundary inside a block
    q, k, v = make_qkv(b=2, s=256, h=2, hkv=2, d=32, seed=7)
    seg = jnp.concatenate(
        [jnp.zeros((2, 100), jnp.int32), jnp.ones((2, 156), jnp.int32)],
        axis=1)
    ref = mha(q, k, v, causal=causal, segment_ids=seg)
    from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention
    out = pallas_flash_attention(q, k, v, causal=causal, segment_ids=seg,
                                 block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pallas_flash_segment_ids_grad(pallas_interpret):
    q, k, v = make_qkv(b=1, s=256, h=2, hkv=2, d=32, seed=8)
    seg = jnp.concatenate(
        [jnp.zeros((1, 96), jnp.int32), jnp.ones((1, 160), jnp.int32)],
        axis=1)
    from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, segment_ids=seg) ** 2)

    def loss_pallas(q, k, v):
        return jnp.sum(pallas_flash_attention(
            q, k, v, causal=True, segment_ids=seg,
            block_q=128, block_kv=128) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_pallas_flash_prefill_offset(pallas_interpret):
    # continuation prefill: 128 queries starting at position 128 of 256 keys
    q, k, v = make_qkv(b=1, s=256, h=2, hkv=2, d=32, seed=6)
    q2 = q[:, 128:]
    ref = mha(q2, k, v, causal=True, q_offset=128)
    from kubeflow_tpu.ops.flash_pallas import pallas_flash_attention
    out = pallas_flash_attention(q2, k, v, causal=True, q_offset=128,
                                 block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the three kinds of tile (interior, diagonal, future): ops/flash_pallas.py
# ---------------------------------------------------------------------------

def _two_documents(sk):
    return jnp.concatenate([jnp.zeros((1, sk * 3 // 8), jnp.int32),
                            jnp.ones((1, sk - sk * 3 // 8), jnp.int32)],
                           axis=1)


# name: (sq, sk, block_q, block_kv, causal, q_offset, traced offset,
#        segmented, q/k head size, the census a head must show)
TILE_CASES = {
    "block_q<block_kv": (512, 512, 128, 256, True, 0, False, False, 32,
                         (2, 4, 2)),
    "block_q=block_kv": (512, 512, 128, 128, True, 0, False, False, 32,
                         (6, 4, 6)),
    "block_q>block_kv": (512, 512, 256, 128, True, 0, False, False, 32,
                         (2, 4, 2)),
    "padded_last_block": (400, 400, 128, 128, True, 0, False, False, 32,
                          (6, 4, 6)),
    # rows past the last key: the padded block lies BELOW the diagonal
    "padded_block_below_the_diagonal": (768, 400, 128, 128, True, 0, False,
                                        False, 32, (12, 6, 6)),
    "q_offset_static": (256, 512, 128, 128, True, 256, False, False, 32,
                        (5, 2, 1)),
    "q_offset_static_off_the_tiles": (256, 512, 128, 128, True, 200, False,
                                      False, 32, (3, 4, 1)),
    "q_offset_traced_0": (256, 512, 128, 128, True, 0, True, False, 32,
                          (1, 2, 5)),
    "q_offset_traced": (256, 512, 128, 128, True, 256, True, False, 32,
                        (5, 2, 1)),
    "sq!=sk": (256, 512, 128, 128, True, 0, False, False, 32, (1, 2, 5)),
    "qk192_v128": (512, 512, 256, 256, True, 0, False, False, 192,
                   (1, 2, 1)),
    "non_causal": (512, 512, 128, 256, False, 0, False, False, 32,
                   (8, 0, 0)),
    "non_causal_padded": (400, 400, 128, 128, False, 0, False, False, 32,
                          (12, 4, 0)),
    "segmented": (512, 512, 128, 128, True, 0, False, True, 32, (0, 10, 6)),
    "segmented_non_causal": (512, 512, 128, 128, False, 0, False, True, 32,
                             (0, 16, 0)),
}


@pytest.mark.parametrize("case", TILE_CASES)
def test_pallas_tile_kinds_match_mha(pallas_interpret, case):
    """Values and the three gradients against ops.attention.mha where each
    kind of tile, and each boundary between them, is forced by the shapes."""
    from kubeflow_tpu.ops import flash_pallas

    (sq, sk, block_q, block_kv, causal, q_offset, traced, segmented, d,
     census) = TILE_CASES[case]
    assert flash_pallas.block_census(
        sq, sk, block_q, block_kv, causal, q_offset,
        segmented=segmented) == census
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (1, sq, 2, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, 2, 128 if d == 192 else d),
                          jnp.float32)
    seg = _two_documents(sk) if segmented else None

    def ref(q, k, v):
        return mha(q, k, v, causal=causal, q_offset=q_offset,
                   segment_ids=seg)

    def pallas(q, k, v, q_offset=q_offset):
        return flash_pallas.pallas_flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, segment_ids=seg,
            block_q=block_q, block_kv=block_kv)

    if traced:
        out = jax.jit(pallas)(q, k, v, jnp.int32(q_offset))
    else:
        out = pallas(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    if traced or q_offset:
        return   # the continuation path is forward-only
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    g_pal = jax.grad(lambda *a: jnp.sum(pallas(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


TILE_GRID = [(sq, sk, bq, bk, causal, off)
             for sq, sk in [(512, 512), (400, 400), (256, 768), (768, 256),
                            (2048, 2048)]
             for bq, bk in [(128, 128), (128, 256), (256, 128), (256, 512),
                            (384, 128)]
             for causal in (True, False)
             for off in ((0, 128, 200) if causal and sq < sk else (0,))]


def _kinds_by_the_mask(sq, sk, bq, bk, causal, off):
    """[n_q, n_k] of 'i', 'd', 'f' from the [sq, sk] mask itself, each
    length padded to its tile: padded keys masked, padded rows not."""
    n_q, n_k = -(-sq // bq), -(-sk // bk)
    q_pos = np.arange(n_q * bq)[:, None] + off
    k_pos = np.arange(n_k * bk)[None, :]
    mask = np.broadcast_to(k_pos < sk, (n_q * bq, n_k * bk))
    if causal:
        mask = mask & (q_pos >= k_pos)
        seen = np.broadcast_to(q_pos >= k_pos, mask.shape)
    else:
        seen = np.ones_like(mask)
    tiles = lambda m: m.reshape(n_q, bq, n_k, bk).transpose(0, 2, 1, 3)
    return np.where(tiles(mask).all((2, 3)), "i",
                    np.where(tiles(seen).any((2, 3)), "d", "f"))


@pytest.mark.parametrize("sq,sk,bq,bk,causal,off", TILE_GRID)
def test_block_census_counts_the_mask(sq, sk, bq, bk, causal, off):
    from kubeflow_tpu.ops.flash_pallas import block_census

    kinds = _kinds_by_the_mask(sq, sk, bq, bk, causal, off)
    want = tuple(int((kinds == c).sum()) for c in "idf")
    assert block_census(sq, sk, bq, bk, causal, off) == want
    # segments can mask any element: every visited tile keeps the mask
    assert block_census(sq, sk, bq, bk, causal, off, segmented=True) == (
        0, want[0] + want[1], want[2])
    assert sum(want) == kinds.size


@pytest.mark.parametrize("sq,sk,bq,bk,causal,off", TILE_GRID)
def test_future_steps_stand_on_a_visited_block(sq, sk, bq, bk, causal, off):
    """The block index a future grid step maps to is that of the visited
    step nearest before it (forward, dQ: KV sequential) or after it (dK/dV:
    q sequential, no offset), so the pipeline issues no copy for it."""
    from kubeflow_tpu.ops.flash_pallas import kv_block_index, q_block_index

    kinds = _kinds_by_the_mask(sq, sk, bq, bk, causal, off)
    n_q, n_k = kinds.shape
    i, j = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    kv = np.asarray(kv_block_index(i, j, bq, bk, causal, off))
    for qi in range(n_q):
        last = max(kj for kj in range(n_k) if kinds[qi, kj] != "f")
        for kj in range(n_k):
            assert kv[qi, kj] == (kj if kinds[qi, kj] != "f" else last)
    if off:
        return
    qb = np.asarray(q_block_index(i, j, bq, bk, n_q, causal))
    for kj in range(n_k):
        visited = [qi for qi in range(n_q) if kinds[qi, kj] != "f"]
        first = min(visited) if visited else n_q - 1
        for qi in range(n_q):
            assert qb[qi, kj] == (qi if kinds[qi, kj] != "f" else first)


# ---------------------------------------------------------------------------
# ring attention: segment_ids + the Pallas ring body (VERDICT r2 missing #2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_ring_segment_ids(devices8, causal):
    # packed batch crossing shard boundaries: docs of 24+40 over a 4-way ring
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=2, s=64, h=4, hkv=4, d=16)
    seg = jnp.concatenate(
        [jnp.zeros((2, 24), jnp.int32), jnp.ones((2, 40), jnp.int32)], axis=1)
    ref = mha(q, k, v, causal=causal, segment_ids=seg)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                 segment_ids=seg, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_pallas_matches_mha(pallas_interpret, devices8, causal):
    # the long-context design point: flash kernel per arriving KV shard
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=2, hkv=2, d=32, seed=11)
    ref = mha(q, k, v, causal=causal)
    out = jax.jit(lambda a, b, c: ring_attention_sharded(
        a, b, c, mesh, causal=causal, impl="pallas"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_pallas_segment_ids(pallas_interpret, devices8):
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=2, s=512, h=2, hkv=2, d=32, seed=12)
    seg = jnp.concatenate(
        [jnp.zeros((2, 200), jnp.int32), jnp.ones((2, 312), jnp.int32)],
        axis=1)
    ref = mha(q, k, v, causal=True, segment_ids=seg)
    out = ring_attention_sharded(q, k, v, mesh, causal=True,
                                 segment_ids=seg, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_pallas_grad_matches_mha(pallas_interpret, devices8):
    # backward = second ring pass reusing the dq/dkv kernels w/ global lse
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=2, hkv=2, d=32, seed=13)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(
            q, k, v, mesh, causal=True, impl="pallas") ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_pallas_gqa(pallas_interpret, devices8):
    # kv stays unexpanded around the ring; expansion per arriving shard
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=4, hkv=2, d=32, seed=14)
    ref = mha(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ring_pallas_segment_ids_grad(pallas_interpret, devices8):
    # the segmented backward ring pass (seg rotates with KV in BOTH passes)
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=2, hkv=2, d=32, seed=15)
    seg = jnp.concatenate(
        [jnp.zeros((1, 200), jnp.int32), jnp.ones((1, 312), jnp.int32)],
        axis=1)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, segment_ids=seg) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(
            q, k, v, mesh, causal=True, segment_ids=seg,
            impl="pallas") ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_ring_pallas_gqa_grad(pallas_interpret, devices8):
    # dk/dv fold back to kv-head width through the rotating accumulators
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=4, hkv=2, d=32, seed=16)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(
            q, k, v, mesh, causal=True, impl="pallas") ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_long_seq_flash_body(pallas_interpret, devices8, causal):
    # past the 256 threshold the post-all-to-all local attention runs the
    # flash path (never dense S x S probs) — parity vs dense mha
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=4, hkv=4, d=32, seed=17)
    ref = mha(q, k, v, causal=causal)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ulysses_long_seq_flash_grad(pallas_interpret, devices8):
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=1, s=512, h=4, hkv=4, d=32, seed=18)

    def loss_ref(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True) ** 2)

    def loss_uly(q, k, v):
        return jnp.sum(ulysses_attention_sharded(q, k, v, mesh,
                                                 causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ulysses_long_seq_gqa_segment_ids(pallas_interpret, devices8):
    # the flash branch (seq >= 256) crossed with GQA expansion AND packed
    # segment_ids gathered to the full-sequence view
    mesh = make_mesh(MeshConfig(sequence=4), devices=devices8)
    q, k, v = make_qkv(b=2, s=512, h=4, hkv=2, d=32, seed=19)
    seg = jnp.concatenate(
        [jnp.zeros((2, 200), jnp.int32), jnp.ones((2, 312), jnp.int32)],
        axis=1)
    ref = mha(q, k, v, causal=True, segment_ids=seg)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=True,
                                    segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
