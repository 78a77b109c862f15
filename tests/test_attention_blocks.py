"""`attend`'s choice of the flash kernel's blocks (ISSUE 31): one pure rule
of (batch, query length, key length, padded head width), total and valid
over everything `_flash_gate` admits. What Mosaic makes of the blocks is
`tests/test_tpu_compile.py`'s; how fast they are is the chip's (PERF.md)."""

import dataclasses

import pytest

from dlrm_flexflow_tpu.ops import attention

WIDTHS = (64, 128, 256, 384)
SEQS = (512, 1024, 1536, 4096, 8192, 32768)


def _kernels(sizes):
    """A BlockSizes as (forward, dkv, dq) tuples of blocks, in the order of
    `_FLASH_KERNELS`' fields."""
    return tuple(tuple(getattr(sizes, f) for f in fields)
                 for fields, *_ in attention._FLASH_KERNELS.values())


@pytest.mark.parametrize("w", WIDTHS)
@pytest.mark.parametrize("sk", SEQS)
@pytest.mark.parametrize("sq", SEQS)
def test_flash_blocks_are_valid_over_the_gates_domain(sq, sk, w):
    # constructing checks minor <= major and minor | major
    sizes, scope = attention._flash_blocks(1, sq, sk, w)
    assert sizes.has_backward_blocks and sizes.block_b == 1
    fwd, dkv, dq = _kernels(sizes)
    for block in (fwd[0], dkv[0], dkv[1], dq[0]):
        assert block % 128 == 0 and sq % block == 0, (block, sq)
    for block in (fwd[1], fwd[2], dkv[2], dkv[3], dq[1], dq[2]):
        assert block % 128 == 0 and sk % block == 0, (block, sk)
    for minor, major in ((fwd[2], fwd[1]), (dkv[1], dkv[0]),
                         (dkv[3], dkv[2]), (dq[2], dq[1])):
        assert minor <= major and major % minor == 0
    # past jax's default of 128, where a grid step is all overhead, but
    # for dq's key blocks (the `di` it broadcasts in HBM is that wide)
    assert min(fwd + dkv + dq[:1]) >= 512
    for blocks, (*_, need) in zip((fwd, dkv, dq),
                                  attention._FLASH_KERNELS.values()):
        assert need(w, *blocks) <= attention.FLASH_VMEM_BYTES
    # the scope says every block, in BlockSizes' order within a kernel
    assert scope == ("flash_fwd_{}_{}_{}.dkv_{}_{}_{}_{}.dq_{}_{}_{}"
                     .format(*fwd, *dkv, *dq))
    assert "/" not in scope and " " not in scope


@pytest.mark.parametrize("heads", [20, 16])
def test_flash_blocks_of_the_two_cells_are_the_sweeps(heads):
    """glm_4_7_flash.s8192_local (20 heads) and qwen3_next_80b_a3b.
    s8192_local (16) attend at (1, heads, 8192, 256): the blocks the sweep
    on the chip chose (PERF.md, PR 31), pinned so that an edit of the rule
    shows. The head count is not the rule's to see."""
    del heads
    sizes, scope = attention._flash_blocks(1, 8192, 8192, 256)
    assert dataclasses.asdict(sizes) == dict(
        block_q=1024, block_k_major=1024, block_k=1024, block_b=1,
        block_q_major_dkv=1024, block_k_major_dkv=1024, block_k_dkv=1024,
        block_q_dkv=512, block_k_major_dq=128, block_k_dq=128,
        block_q_dq=2048)
    assert scope == ("flash_fwd_1024_1024_1024.dkv_1024_512_1024_1024"
                     ".dq_2048_128_128")


def test_a_wider_head_gets_smaller_blocks_and_a_narrower_no_larger():
    at = {w: _kernels(attention._flash_blocks(1, 8192, 8192, w)[0])
          for w in WIDTHS}
    assert at[64] == at[128] == at[256]
    for narrow, wide in zip(at[256], at[384]):
        assert all(a >= b for a, b in zip(narrow, wide))
    assert at[384] != at[256]


def test_the_batch_does_not_move_the_blocks():
    assert (attention._flash_blocks(1, 4096, 4096, 128)
            == attention._flash_blocks(8, 4096, 4096, 128))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_route_at_the_chosen_blocks_agrees_with_the_dense(monkeypatch,
                                                                causal):
    """jax's kernel under the TPU interpreter, through `attend` as a model
    reaches it: grouped K/V heads, a value head narrower than the keys
    (padded), a sequence of three blocks of 512 (the diagonal's skipped
    blocks and the stay-in-place index maps), forward and gradients. bf16
    in, so the dense route on the same inputs is the measure."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    import dlrm_flexflow_tpu as ff
    from dlrm_flexflow_tpu.ops import embedding
    monkeypatch.setattr(embedding, "_pallas_common",
                        lambda model, op_name, width_ok: bool(width_ok))
    monkeypatch.setattr(attention, "_scores_fit", lambda *a: False)

    class Model:
        ops, optimizer, mesh = [], ff.AdamOptimizer(), None
        config = ff.FFConfig()

    s = 1536
    assert _kernels(attention._flash_blocks(1, s, s, 128)[0])[0][0] == 512
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, s, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 1, s, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 1, s, 64), jnp.bfloat16)

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v).astype(jnp.float32)
            return jnp.sum(out * jnp.cos(out)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    with pltpu.force_tpu_interpret_mode():
        got, got_g = run(lambda q, k, v: attention.attend(
            Model(), "attn", q, k, v, causal))
    want, want_g = run(lambda q, k, v: attention._attention_local(
        q, k, v, causal))
    assert got.shape == want.shape == (1, 2, s, 64)
    assert float(jnp.max(jnp.abs(got - want))) < 1.6e-2   # a bf16 ulp at 2
    for g, w_ in zip(got_g, want_g):
        g, w_ = g.astype(jnp.float32), w_.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(g - w_)) / jnp.max(jnp.abs(w_))) < 1.5e-2
