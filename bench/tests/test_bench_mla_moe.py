"""The DeepSeek-V2 family (``bench/families/mla_moe.py``) and the MoE
cell's readers: the configuration keeps the published widths, the FLOP
count against a hand count, the program's configuration from the file,
the new per-layer readers on a synthetic trace, and the MoE cell's run at
a small size on the CPU, sound and with a fault planted underneath."""
import contextlib
import dataclasses
import sys

import jax
import pytest

from bench import common as C
from bench import faults
from bench import run as R
from bench import scopes as SC
from bench.tests import tiny

CELL = "deepseek-v2-lite-16b.train-moe-8k"
FAM = C.load_family("mla_moe")


def _cfg():
    return C.load_json(C.BENCH / "configs" / "deepseek-v2-lite-16b-6l-8e.json")


def test_published_widths_and_the_cut():
    """Every width, the 64-wide router and its top-6 as published; the
    cut is depth, the experts held and the vocabulary, as ``reduced``
    lists them."""
    c = _cfg()
    published = {
        "hidden_size": 2048, "intermediate_size": 10944,
        "moe_intermediate_size": 1408, "num_attention_heads": 16,
        "num_key_value_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "first_k_dense_replace": 1, "norm_topk_prob": False,
        "scoring_func": "softmax", "seq_aux": True, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "routed_scaling_factor": 1,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    for k, v in published.items():
        assert c[k] == v, k
    assert sorted(c["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                    "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (6, 8, 12800)
    assert c["n_router_experts"] == 64
    a = FAM.arch(c)
    assert (a.moe.n_experts, a.moe.n_held, a.moe.top_k) == (64, 8, 6)
    assert a.ffns() == ("mlp",) + ("moe",) * 5
    shapes = FAM.canonical_shapes(c)
    assert shapes["layers.moe.router"] == (5, 2048, 64)
    assert shapes["layers.moe.experts.w_up"] == (5, 8, 2048, 2 * 1408)
    assert shapes["lm_head"] == (2048, 12800)


def test_flops_hand_count():
    c = _cfg()
    d, H = 2048, 16
    mla = d * H * 192 + d * 576 + 512 * H * (128 + 128) + H * 128 * d
    moe = d * 64 + 3 * d * 1408 * (2 + 6 * 8 / 64)
    weights = 6 * mla + 3 * d * 10944 + 5 * moe + d * 12800
    assert FAM.matmul_weights(c) == weights
    attention = 6 * H * (192 + 128) * (8192 + 1)
    assert FAM.train_flops_per_token(c, 8192) == pytest.approx(
        3 * (2 * weights + attention), rel=1e-12)


def test_arch_refuses_what_the_program_does_not_run():
    c = _cfg()
    for key, bad in (("scoring_func", "sigmoid"), ("topk_method", "noaux"),
                     ("n_group", 8), ("q_lora_rank", 1536)):
        with pytest.raises(ValueError, match=key):
            FAM.arch(dict(c, **{key: bad}))


SMAP = {"custom-call.1": ("custom-call", "experts"),
        "custom-call.2": ("custom-call", "attn_core"),
        "fusion.3": ("fusion", "experts"),
        "fusion.4": ("fusion", "moe"),
        "fusion.5": ("fusion", "mla")}
OPS = {"custom-call.1 bf16[98304,2816]": 2.0,
       "custom-call.2 bf16[2,16,8192,256]": 3.0,
       "fusion.3 bf16[98304,1408]": 0.5,
       "fusion.4 f32[16384,64]": 1.5,
       "fusion.5 bf16[2,8192,3072]": 1.0}


@pytest.mark.parametrize("layer,want", [("experts", 5.0), ("moe", 3.0),
                                        ("mla", 2.0)])
def test_layer_shares(monkeypatch, layer, want):
    monkeypatch.setattr(SC, "run_map", lambda: SMAP)
    ctx = {"trace": {"ops": OPS, "window_s": 50.0}}
    assert C.load_reader(f"device_share.{layer}.train")(ctx) == \
        pytest.approx(want)


def test_experts_roofline(monkeypatch):
    """The least time of the window's grouped-product work over the
    seconds of the ``experts`` custom calls (fusions left out)."""
    monkeypatch.setattr(SC, "run_map", lambda: SMAP)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    tokens = 32768 * 10
    flops, bytes_ = FAM.experts_kernel_work(_cfg(), tokens)
    least = max(flops / peak["bf16_flops_per_s"],
                bytes_ / peak["hbm_bytes_per_s"])
    ctx = {"trace": {"ops": OPS, "window_s": 50.0}, "tokens": tokens,
           "peak": peak}
    got = C.load_reader("experts_kernel_roofline.train")(ctx)
    assert got == pytest.approx(100 * least / 2.0)
    # per MoE layer, rows = tokens x 6 x 8/64, up (2048 -> 2816) and down
    # (1408 -> 2048), each four times: forward, recompute, dX, dW
    rows = tokens * 0.75
    assert flops == pytest.approx(5 * 4 * 2 * rows * 3 * 2048 * 1408)
    assert flops / peak["bf16_flops_per_s"] > bytes_ / peak[
        "hbm_bytes_per_s"]              # bound by compute at these rows


def test_experts_roofline_reads_nothing_without_kernel_calls(monkeypatch):
    monkeypatch.setattr(SC, "run_map", lambda: SMAP)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    ctx = {"trace": {"ops": {"fusion.3 bf16[8]": 1.0}, "window_s": 1.0},
           "tokens": 1, "peak": {}}
    assert C.load_reader("experts_kernel_roofline.train")(ctx) is None


# Limits at this small size, as bench/tests/test_bench_faults.py's; the
# cell's own limits are set from chip runs at its size.
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 8e-3, "change_gap": 4e-3}


@contextlib.contextmanager
def renormalised_gates():
    """The program with the top-k gates renormalised to sum to 1 (the
    softmax of the top-k logits), where the reference keeps DeepSeek-V2's
    unrenormalised softmax probabilities: the comparison has to see the
    gate's form."""
    from repro.layers import moe
    gates = moe._topk_gates

    def renormalised(logits, mc):
        return gates(logits, dataclasses.replace(mc, norm_topk=True))
    with faults._patched(moe, "_topk_gates", renormalised):
        yield


PLANTED = {None: contextlib.nullcontext,
           "half_batch": faults.half_batch,
           "state_unchanged": faults.state_unchanged,
           "renormalised_gates": renormalised_gates}


@pytest.mark.parametrize("fault", list(PLANTED))
def test_moe_cell_is_correct_only_without_a_fault(fault):
    """The MoE cell's driver, options and microbatches at the size of the
    family's tiny chip-share variant: a sound run is correct, and the run
    with half the batch, with the state unchanged or with renormalised
    gates is not."""
    cell = tiny.cell(CELL, tiny.config("mla-yarn-moe-held-8-of-16"),
                     TRAIN_LIMITS)
    with PLANTED[fault]():
        out, checks = R.execute(cell, 2**31 + 3, 0.3, False,
                                jax.devices()[:1])
    assert out["correct"] == (fault is None), checks
