"""The Pallas flash kernel of ``kernels/tpu_attention`` against the jnp
attention core, and the dispatch between them in ``attn_core``.

The kernel runs here in Pallas's HLO interpret mode
(``force_tpu_interpret_mode(True)``; the callback-based TPU simulator has
effects that ``jax.checkpoint`` refuses), at tiny shapes: forward
and the gradients of q, k and v under ``jax.checkpoint`` (full remat, as
the decoder applies it), causal and not, at head dims 64 and 128 and at
MLA's 192 with a narrower v and its own scale, over one block and
several. ``MAX_BLOCK`` is lowered in a test to tile a
short sequence into several blocks.

The tolerance is fixed by the dtype, not fitted: both sides take bf16
q, k and v and return bf16, and the kernel also rounds P and dS to
bf16 for its MXU products, as the TPU does with the jnp core's fp32
operands at the default precision. The gap is then a few bf16 roundings
(2**-8 each) of sums over the sequence, so 2**-7 of the reference's norm
bounds it; a wrong mask or scale moves it to order 1.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_attention as TA
from repro.layers import attention as A

RTOL = 2.0 ** -7


def _qkv(B, T, H, d, *, nkv=None, dv=None, Tk=None, dtype=jnp.bfloat16,
         seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    Tk, nkv, dv = Tk or T, nkv or H, dv or d
    q = jax.random.normal(ks[0], (B, T, H, d), dtype)
    k = jax.random.normal(ks[1], (B, Tk, nkv, d), dtype)
    v = jax.random.normal(ks[2], (B, Tk, nkv, dv), dtype)
    do = jax.random.normal(ks[3], (B, T, H, dv), jnp.float32)
    return q, k, v, do


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _jnp_core(q, k, v, causal, scale=None):
    return A._attn_core_dense(q, k, v, causal=causal, window=0, q_pos0=0,
                              scale=scale)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("n_blocks", [1, 2])
def test_kernel_matches_jnp_core(monkeypatch, causal, d, n_blocks):
    """Forward output and the q, k, v gradients under full remat."""
    monkeypatch.setitem(TA.MAX_BLOCK, d, TA.MIN_BLOCK)
    T = TA.MIN_BLOCK * n_blocks
    q, k, v, do = _qkv(1, T, 2, d, seed=n_blocks)
    assert T // TA.block_size(T, d) == n_blocks

    def loss(core):
        f = jax.checkpoint(lambda q, k, v: core(q, k, v).astype(jnp.float32))
        return lambda q, k, v: jnp.sum(f(q, k, v) * do)

    kernel = lambda q, k, v: TA.flash_mha(q, k, v, causal=causal)  # noqa
    oracle = lambda q, k, v: _jnp_core(q, k, v, causal)  # noqa
    with pltpu.force_tpu_interpret_mode(True):
        out = jax.jit(kernel)(q, k, v)
        grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    ref = oracle(q, k, v)
    ref_grads = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    assert out.dtype == q.dtype and out.shape == q.shape
    assert _rel(out, ref) < RTOL
    for g, r in zip(grads, ref_grads):
        assert g.dtype == r.dtype and g.shape == r.shape
        assert _rel(g, r) < RTOL


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("n_blocks", [1, 2])
def test_mla_head_matches_jnp_core(monkeypatch, causal, n_blocks):
    """MLA's core (DeepSeek-V2: q/k head 192 = 128 nope + 64 rope, v head
    128, the YaRN softmax scale mscale^2 / sqrt(192) = 0.114721): q and k
    run zero-padded to the kernel's head dim 256, v too, and the output
    is sliced back to 128 columns; forward and the q, k, v gradients
    under full remat against the jnp core at the same scale."""
    d, dv, scale = 192, 128, 0.114721
    assert TA.kernel_dim(d) == 256
    monkeypatch.setitem(TA.MAX_BLOCK, 256, TA.MIN_BLOCK)
    T = TA.MIN_BLOCK * n_blocks
    q, k, v, do = _qkv(1, T, 2, d, dv=dv, seed=10 + n_blocks)
    assert TA.supports(q, k, v, scale=scale)
    assert T // TA.block_size(T, d) == n_blocks

    def loss(core):
        f = jax.checkpoint(lambda q, k, v: core(q, k, v).astype(jnp.float32))
        return lambda q, k, v: jnp.sum(f(q, k, v) * do)

    kernel = lambda q, k, v: TA.flash_mha(q, k, v, causal=causal,  # noqa
                                          scale=scale)
    oracle = lambda q, k, v: _jnp_core(q, k, v, causal, scale)  # noqa
    with pltpu.force_tpu_interpret_mode(True):
        out = jax.jit(kernel)(q, k, v)
        grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    ref = oracle(q, k, v)
    ref_grads = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    assert out.dtype == q.dtype and out.shape == (1, T, 2, dv)
    assert _rel(out, ref) < RTOL
    for g, r in zip(grads, ref_grads):
        assert g.dtype == r.dtype and g.shape == r.shape
        assert _rel(g, r) < RTOL
    # the scale is the one given: the default 1/sqrt(192) is another core
    assert _rel(_jnp_core(q, k, v, causal), ref) > 10 * RTOL


def test_block_rule():
    """The largest power of two up to MAX_BLOCK[d] that divides T."""
    assert TA.block_size(2048, 64) == TA.MAX_BLOCK[64] == 1024
    assert TA.block_size(2048, 128) == TA.MAX_BLOCK[128] == 512
    assert TA.block_size(384, 64) == 128
    assert TA.block_size(4096 + 512, 64) == 512
    assert TA.block_size(100, 64) == 0       # under the smallest block
    assert TA.block_size(2048 + 64, 64) == 0  # not a block multiple
    assert TA.block_size(2048, 96) == 0      # a head dim the rule lacks
    # wider heads run padded to whole 128 lanes: MLA's 192 as 256
    assert TA.kernel_dim(64) == 64 and TA.kernel_dim(128) == 128
    assert TA.kernel_dim(192) == TA.kernel_dim(256) == 256
    assert TA.block_size(8192, 192) == TA.MAX_BLOCK[256]


def _takes_kernel(q, k, v, **kw):
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: A.attn_core(q, k, v, **kw))(
        q, k, v))
    return "pallas_call" in jaxpr


@pytest.mark.parametrize("T", [128, 2048, 4096 + 512])
@pytest.mark.parametrize("causal", [True, False])
def test_dispatch_takes_kernel(T, causal):
    """bf16 self-attention from 0, MHA, no window, default scale, a
    tiled length: the kernel, below the dense threshold and above it."""
    q, k, v, _ = _qkv(1, T, 2, 64)
    assert TA.supports(q, k, v)
    assert _takes_kernel(q, k, v, causal=causal)


@pytest.mark.parametrize("case", ["mla", "scale"])
def test_dispatch_takes_kernel_for_mla(case):
    """MLA's narrower v with its own scale at its 192-wide q/k head, and a
    passed scale alone: the kernel too."""
    if case == "mla":
        q, k, v, _ = _qkv(1, 256, 2, 192, dv=128)
        kw = {"scale": 0.114721}
    else:
        q, k, v, _ = _qkv(1, 256, 2, 64)
        kw = {"scale": 64 ** -0.5}
    assert TA.supports(q, k, v, **kw)
    assert _takes_kernel(q, k, v, causal=True, **kw)


@pytest.mark.parametrize("case", [
    "gqa", "window", "mla", "cross", "offset", "untiled", "fp32",
    "head_dim"])
def test_dispatch_falls_back(case):
    """Every call the kernel would compute differently keeps the jnp
    core: GQA, a sliding window, a v head wider than q's, cross-attention
    (Tq != Tk), a cached prefill at an offset, a length no block tiles,
    fp32 operands, a head dim the block rule lacks."""
    B, T, H, d = 1, 256, 4, 64
    kw = {}
    shape = dict(B=B, T=T, H=H, d=d)
    if case == "gqa":
        shape["nkv"] = 2
    elif case == "window":
        kw["window"] = 64
    elif case == "mla":
        shape["dv"] = 128
        kw["scale"] = 0.1
    elif case == "cross":
        shape["Tk"] = 384
        kw["causal"] = False
    elif case == "offset":
        kw["q_pos0"] = 128
    elif case == "untiled":
        shape["T"] = 200
    elif case == "fp32":
        shape["dtype"] = jnp.float32
    elif case == "head_dim":
        shape["d"] = 32
    q, k, v, _ = _qkv(**shape)
    assert not TA.supports(q, k, v, **{n: x for n, x in kw.items()
                                       if n != "causal"})
    assert not _takes_kernel(q, k, v, **kw)


@pytest.mark.parametrize("T", [256, 4096 + 512])
def test_cpu_runs_the_jnp_core(T):
    """Where the kernel is staged, a program lowered for the CPU runs the
    jnp core, bit for bit, with no TPU custom call in it."""
    q, k, v, _ = _qkv(1, T, 2, 64)
    f = jax.jit(lambda q, k, v: A.attn_core(q, k, v, causal=True))
    assert "tpu_custom_call" not in f.lower(q, k, v).as_text()
    jnp_core = (A.attn_core_chunked(q, k, v, causal=True) if T > 2048
                else _jnp_core(q, k, v, True))
    assert jnp.array_equal(f(q, k, v), jnp_core)
