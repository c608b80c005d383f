"""A configuration file, read into the sizes the benchmark runs.

``Dims`` is what the reference and the weight makers need: hashable, so it
can be a static argument of a jitted call.  ``program_config`` turns it
into the program's own ``ArchConfig``; the rest of the benchmark uses
``Dims`` alone.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    program: str              # the program's own name for the architecture
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int                 # dense FFN width, or one expert's width
    vocab: int
    experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    qk_norm: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    rank: int = 8
    alpha: float = 32.0
    lora_targets: tuple = ("q_proj", "v_proj")
    aux_weight: float = 0.01  # the trained loss's load-balance coefficient

    @property
    def lora_scale(self) -> float:
        return self.alpha / self.rank

    def targets(self):
        """(projection, d_out) of every LoRA target, in a fixed order."""
        width = {"q_proj": self.heads * self.head_dim,
                 "k_proj": self.kv_heads * self.head_dim,
                 "v_proj": self.kv_heads * self.head_dim,
                 "o_proj": self.d_model}
        return [(t, width[t]) for t in self.lora_targets]


def load(name: str, root: Path = HERE) -> Dims:
    """Read ``configs/<name>.json``."""
    c = json.loads((root / "configs" / f"{name}.json").read_text())
    a = c["adapter"]
    moe = "num_experts" in c
    heads = c["num_attention_heads"]
    return Dims(
        name=c["name"], program=c["program_config"],
        layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        heads=heads, kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["moe_intermediate_size"] if moe else c["intermediate_size"],
        vocab=c["vocab_size"],
        experts=c.get("num_experts", 0), top_k=c.get("num_experts_per_tok", 0),
        capacity_factor=1.25 if moe else 0.0,
        qk_norm=c["architectures"][0].startswith("Qwen3"),
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        rank=a["lora_rank"], alpha=a["lora_alpha"],
        lora_targets=tuple(a["lora_targets"]))


def program_config(d: Dims):
    """The program's ArchConfig for these sizes: its registered
    architecture with every size, the rotary base and the adapter
    settings as run (for the benchmark's configurations only the depth,
    the rotary base and the dropout differ from the registered ones)."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(d.program), n_layers=d.layers, d_model=d.d_model,
        n_heads=d.heads, n_kv_heads=d.kv_heads, d_head=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab, n_experts=d.experts,
        top_k=d.top_k, qk_norm=d.qk_norm, rope_theta=d.rope_theta,
        norm_eps=d.norm_eps, lora_rank=d.rank, lora_alpha=d.alpha,
        lora_targets=d.lora_targets, lora_dropout=0.0,
        **({"capacity_factor": d.capacity_factor} if d.experts else {}))
