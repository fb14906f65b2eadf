"""Architecture configuration system.

Every assigned architecture is expressed as an :class:`ArchConfig`; the
decoder/enc-dec assemblers in this package consume only the config, so new
architectures are pure data. ``reduced()`` produces the small same-family
variant used by the CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from repro.core.comm_model import Constraints, LayerShape


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25    # only with an expert axis (g_expert > 1)
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    score_fn: str = "softmax"        # "softmax" | "sigmoid" (dsv3)
    norm_topk: bool = True           # renormalise the top-k gates to sum 1
    routed_scale: float = 1.0
    aux_form: str = "switch"         # "switch" (batch) | "seq" (dsv2)
    n_held: int = 0                  # experts this model holds; 0 = all
    first_dense: int = 0             # leading dense layers
    period: int = 1                  # MoE every `period` layers (jamba: 2)

    @property
    def n_bank(self) -> int:
        """Routed experts whose weights this model holds (the chip's
        share of a stated deployment): global ids 0 .. n_bank - 1 of the
        router's ``n_experts``."""
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLACfg:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnCfg:
    """YaRN rotary scaling (arXiv:2309.00071), as DeepSeek-V2 configures
    it (``rope_scaling`` of type ``yarn``)."""
    factor: float
    original_max_pos: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MambaCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                 # 0 -> ceil(d_model / 16)


@dataclasses.dataclass(frozen=True)
class XLSTMCfg:
    proj_factor_mlstm: float = 2.0
    proj_factor_slstm: float = 4.0 / 3.0
    conv_kernel: int = 4


@dataclasses.dataclass(frozen=True)
class EncoderCfg:
    """Whisper audio encoder / VLM vision-stub settings."""
    n_layers: int = 0                # 0: frontend only (vlm)
    n_ctx: int = 1500                # encoder positions / image tokens
    input_dim: int = 0               # stub embedding dim (0 = d_model)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                   # dense|moe|hybrid|ssm|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "silu"                # silu | gelu | squared_relu
    gated_mlp: bool = True
    qk_norm: bool = False
    sliding_window: int = 0          # 0 = full attention
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnCfg] = None
    rotary_pct: float = 1.0
    attn_bias: bool = False
    mlp_bias: bool = False
    tie_embeddings: bool = False
    max_seq: int = 131072
    moe: Optional[MoECfg] = None
    mla: Optional[MLACfg] = None
    mamba: Optional[MambaCfg] = None
    xlstm: Optional[XLSTMCfg] = None
    encoder: Optional[EncoderCfg] = None
    mixer_pattern: Tuple[str, ...] = ()   # per-layer mixer kinds (or period)
    ffn_pattern: Tuple[str, ...] = ()     # explicit per-layer ffn kinds
    mtp_depth: int = 0               # deepseek-v3 multi-token prediction
    source: str = ""                 # citation

    # ------------------------------------------------------------------ #
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a 512 multiple so the LM head / embedding shard
        over any (y, z) factorization; the padded columns are masked in
        vocab_parallel_xent."""
        return -(-self.vocab_size // 512) * 512

    def mixers(self) -> Tuple[str, ...]:
        """Per-layer mixer kind, length n_layers."""
        if self.mixer_pattern:
            p = self.mixer_pattern
            if self.n_layers % len(p):
                raise ValueError("mixer_pattern must divide n_layers")
            return tuple(p) * (self.n_layers // len(p))
        if self.xlstm is not None:
            return tuple("slstm" if i % 8 == 7 else "mlstm"
                         for i in range(self.n_layers))
        if self.mla is not None:
            return ("mla",) * self.n_layers
        return ("attn",) * self.n_layers

    def ffns(self) -> Tuple[str, ...]:
        """Per-layer FFN kind ('mlp' | 'moe' | 'none'), length n_layers."""
        if self.ffn_pattern:
            p = self.ffn_pattern
            if self.n_layers % len(p):
                raise ValueError("ffn_pattern must divide n_layers")
            return tuple(p) * (self.n_layers // len(p))
        if self.xlstm is not None:
            return ("none",) * self.n_layers  # xLSTM blocks are self-contained
        out = []
        for i in range(self.n_layers):
            if (self.moe is not None and i >= self.moe.first_dense
                    and (i - self.moe.first_dense) % self.moe.period == 0):
                out.append("moe")
            else:
                out.append("mlp")
        return tuple(out)

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        return tuple(zip(self.mixers(), self.ffns()))

    def scan_period(self) -> int:
        """Smallest repeating period of layer kinds (for stacked scan)."""
        kinds = self.layer_kinds()
        for p in range(1, self.n_layers + 1):
            if self.n_layers % p:
                continue
            if kinds == kinds[:p] * (self.n_layers // p):
                return p
        return self.n_layers

    def with_segment_counts(self, counts: Tuple[int, ...]) -> "ArchConfig":
        """Depth-reduced variant: segment s repeated counts[s] times
        (used by the dry-run's probe lowerings for exact per-depth HLO
        cost extrapolation)."""
        segs = self.segments()
        assert len(counts) == len(segs)
        mix, ffn = [], []
        for (kinds, _), c in zip(segs, counts):
            for _ in range(c):
                for m, f in kinds:
                    mix.append(m)
                    ffn.append(f)
        return dataclasses.replace(
            self, n_layers=len(mix), mixer_pattern=tuple(mix),
            ffn_pattern=tuple(ffn))

    def segments(self) -> Tuple[Tuple[Tuple[Tuple[str, str], ...], int], ...]:
        """Greedy segmentation of layer_kinds() into (period_kinds,
        n_periods) runs, so e.g. DeepSeek-V3's 3-dense prefix + 58 MoE
        body becomes two scanned segments instead of 61 distinct layers.
        At each layer the run of a repeating period (two periods or more)
        that covers the most layers is taken, the shorter period on a
        tie; where no period repeats, the layer stands alone (a prefix is
        never fused with the run after it into one unscanned period)."""
        kinds = self.layer_kinds()
        n = len(kinds)
        segs = []
        i = 0
        while i < n:
            best = (1, 1)
            for p in range(1, min(8, n - i) + 1):
                pat = kinds[i:i + p]
                r = 1
                while kinds[i + r * p: i + (r + 1) * p] == pat:
                    r += 1
                if r > 1 and (best[1] == 1 or p * r > best[0] * best[1]):
                    best = (p, r)
            p, r = best
            segs.append((kinds[i:i + p], r))
            i += p * r
        return tuple(segs)

    # ------------------------------------------------------------------ #
    def param_count(self) -> int:
        """Approximate parameter count (for docs / comm-model weighting)."""
        d, v = self.d_model, self.vocab_size
        hd = self.head_dim_
        total = v * d * (1 if self.tie_embeddings else 2)
        if self.arch_type == "audio" and self.encoder:
            # encoder stack: self-attn (4 d^2) + mlp (2 d d_ff)
            total += self.encoder.n_layers * (4 * d * d + 2 * d * self.d_ff)
            # decoder cross-attention adds q,k,v,o per layer
            total += self.n_layers * 4 * d * d
        for mixer, ffn in self.layer_kinds():
            if mixer == "attn":
                total += d * (self.n_heads + 2 * self.n_kv_heads) * hd
                total += self.n_heads * hd * d
            elif mixer == "mla":
                m = self.mla
                qk = m.qk_nope_dim + m.qk_rope_dim
                total += d * (m.q_lora_rank or 0)
                total += (m.q_lora_rank or d) * self.n_heads * qk
                total += d * (m.kv_lora_rank + m.qk_rope_dim)
                total += m.kv_lora_rank * self.n_heads * (
                    m.qk_nope_dim + m.v_dim)
                total += self.n_heads * m.v_dim * d
            elif mixer == "mamba":
                di = self.mamba.expand * d
                dtr = self.mamba.dt_rank or -(-d // 16)
                total += d * 2 * di + di * (dtr + 2 * self.mamba.d_state)
                total += dtr * di + di * d
            elif mixer == "mlstm":
                di = int(self.xlstm.proj_factor_mlstm * d)
                total += d * 2 * di + 3 * di * (di // self.n_heads) + di * d
            elif mixer == "slstm":
                dff = -(-int(self.xlstm.proj_factor_slstm * d) // 64) * 64
                total += 4 * d * d + 4 * d * (d // self.n_heads)
                total += d * d + 2 * d * dff + dff * d
            if ffn == "mlp":
                mult = 2 if self.gated_mlp else 1
                total += (mult + 1) * d * self.d_ff
            elif ffn == "moe":
                mc = self.moe
                mult = 2 if self.gated_mlp else 1
                per = (mult + 1) * d * mc.d_expert
                total += mc.n_bank * per + mc.n_shared * per + d * mc.n_experts
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared only)."""
        if self.moe is None:
            return self.param_count()
        mc = self.moe
        mult = 2 if self.gated_mlp else 1
        per = (mult + 1) * self.d_model * mc.d_expert
        n_moe = sum(1 for f in self.ffns() if f == "moe")
        held_active = mc.top_k * mc.n_bank / mc.n_experts
        inactive = int(n_moe * (mc.n_bank - held_active) * per)
        return self.param_count() - inactive

    # ------------------------------------------------------------------ #
    def comm_layers(self) -> Tuple[LayerShape, ...]:
        """LayerShapes for the communication model (paper §5)."""
        d = self.d_model
        hd = self.head_dim_
        out = []
        for mixer, ffn in self.layer_kinds():
            if mixer in ("attn", "mla"):
                nq = self.n_heads * hd
                if mixer == "mla":
                    m = self.mla
                    nq = self.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
                    out.append(LayerShape(d, nq))
                    out.append(LayerShape(self.n_heads * m.v_dim, d,
                                          transposed=True))
                else:
                    # the QKV projection's kv_ring_width prices the
                    # context-parallel KV circulation (2*kv_heads*hd
                    # elements per token forwarded per ring hop)
                    out.append(LayerShape(
                        d, (self.n_heads + 2 * self.n_kv_heads) * hd,
                        kv_ring_width=2 * self.n_kv_heads * hd))
                    out.append(LayerShape(self.n_heads * hd, d,
                                          transposed=True))
            elif mixer == "mamba":
                di = self.mamba.expand * d
                out.append(LayerShape(d, 2 * di))
                out.append(LayerShape(di, d, transposed=True))
            elif mixer == "mlstm":
                di = int(self.xlstm.proj_factor_mlstm * d)
                out.append(LayerShape(d, 2 * di))
                out.append(LayerShape(di, d, transposed=True))
            elif mixer == "slstm":
                dff = -(-int(self.xlstm.proj_factor_slstm * d) // 64) * 64
                out.append(LayerShape(d, 4 * d))
                out.append(LayerShape(d, d, transposed=True))
                out.append(LayerShape(d, 2 * dff))
                out.append(LayerShape(dff, d, transposed=True))
            if ffn == "mlp":
                mult = 2 if self.gated_mlp else 1
                out.append(LayerShape(d, mult * self.d_ff))
                out.append(LayerShape(self.d_ff, d, transposed=True))
            elif ffn == "moe":
                mc = self.moe
                mult = 2 if self.gated_mlp else 1
                # per-token activated expert width (+ shared)
                fa = mc.top_k * mc.d_expert + mc.n_shared * mc.d_expert
                # expert=True: the routed bank shards over g_expert (no
                # expert-axis grad allreduce); a2a_width on the up-proj
                # prices the dispatch+combine all-to-all once per MoE
                # block (capacity slots x d elements per token)
                out.append(LayerShape(
                    d, mult * fa, expert=True,
                    a2a_width=mc.capacity_factor * mc.top_k * d))
                out.append(LayerShape(fa, d, transposed=True, expert=True))
        return tuple(out)

    def tp_constraints(self, global_batch: int) -> Constraints:
        divs = [self.d_model, self.d_ff or self.d_model]
        # kv heads may be *duplicated* over y (kv_layout), so y is only
        # constrained by q heads (+ experts); duplication beyond kv heads
        # wastes KV-cache memory, so the optimizer still prefers small y.
        y_divs = [self.n_heads]
        if self.moe:
            y_divs.append(self.moe.n_bank)
        if self.xlstm:
            y_divs = [self.n_heads]
        return Constraints(global_batch=global_batch,
                           x_divides=tuple(divs),
                           y_divides=tuple(y_divs))

    def axes_ok(self, axes) -> Optional[str]:
        if self.d_model % axes.gx:
            return f"d_model {self.d_model} % gx {axes.gx}"
        if self.n_heads % axes.gy:
            return f"heads {self.n_heads} % gy {axes.gy}"
        if (self.mla is None and self.n_kv_heads % axes.gy
                and axes.gy % self.n_kv_heads):
            return f"kv heads {self.n_kv_heads} vs gy {axes.gy}"
        if self.moe and self.moe.n_bank % axes.gy:
            return f"experts {self.moe.n_bank} % gy {axes.gy}"
        if axes.gseq > 1:
            # context parallelism needs softmax attention everywhere:
            # recurrent mixers (mamba/xlstm) and MLA's materialized path
            # mix across the full sequence on-device and would silently
            # truncate to the local shard
            if set(self.mixers()) != {"attn"}:
                return (f"seq axis (g_seq={axes.gseq}) needs all-attention "
                        f"mixers, got {sorted(set(self.mixers()))}")
            if self.arch_type in ("vlm", "audio"):
                return (f"seq axis unsupported for arch_type "
                        f"{self.arch_type} (contiguous-prefix inputs)")
            if self.max_seq % axes.gseq:
                return f"max_seq {self.max_seq} % g_seq {axes.gseq}"
        if axes.gseq > 1 and self.moe and self.moe.aux_form == "seq":
            return (f"the sequence-wise balance loss needs whole sequences "
                    f"on a chip, not a seq axis of g_seq={axes.gseq}")
        if axes.gexpert > 1:
            if self.moe is None:
                return (f"expert axis (g_expert={axes.gexpert}) needs an "
                        f"MoE architecture")
            if self.moe.n_bank % (axes.gy * axes.gexpert):
                return (f"experts {self.moe.n_bank} % gy*g_expert "
                        f"{axes.gy * axes.gexpert}")
        return None

    def validate_axes(self, axes) -> None:
        err = self.axes_ok(axes)
        assert err is None, f"{self.name}: {err}"

    # ------------------------------------------------------------------ #
    def reduced(self) -> "ArchConfig":
        """2-layer, d_model<=512, <=4-expert variant for CPU smoke tests."""
        n_layers = max(2, self.scan_period())
        if n_layers > 8:
            n_layers = 2
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4)
        kv = min(self.n_kv_heads, heads)
        kw = dict(
            name=self.name + "-smoke", n_layers=n_layers, d_model=d,
            n_heads=heads, n_kv_heads=kv, head_dim=d // heads,
            d_ff=(min(self.d_ff, 512) if self.d_ff else 0),
            vocab_size=min(self.vocab_size, 512), max_seq=512,
        )
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=min(self.moe.top_k, 2),
                d_expert=128, n_shared=min(self.moe.n_shared, 1),
                first_dense=min(self.moe.first_dense, 1)
                if n_layers > 1 else 0)
        if self.mla:
            kw["mla"] = MLACfg(kv_lora_rank=64, q_lora_rank=(
                32 if self.mla.q_lora_rank else 0), qk_nope_dim=32,
                qk_rope_dim=16, v_dim=32)
            kw["head_dim"] = 0
        if self.encoder:
            kw["encoder"] = dataclasses.replace(
                self.encoder, n_layers=min(self.encoder.n_layers, 2),
                n_ctx=16 if self.arch_type == "vlm" else 64,
                input_dim=min(self.encoder.input_dim, 96)
                if self.encoder.input_dim else 0)
        if self.xlstm is not None:
            # one of each cell kind; the full 7:1 period would blow the
            # 1-core CPU collective-rendezvous budget in smoke tests
            kw["mixer_pattern"] = ("mlstm", "slstm")
            kw["n_layers"] = 2
        elif self.mixer_pattern:
            kw["mixer_pattern"] = tuple(
                m for m, _ in self.layer_kinds())[:n_layers]
        if self.sliding_window:
            kw["sliding_window"] = 64
        return dataclasses.replace(self, **kw)
