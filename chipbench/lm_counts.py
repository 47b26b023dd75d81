"""Matrix products one training step of a language-model cell needs, from
the configuration's shapes and the batch's routing (nothing traced,
compiled or run): what `counts.jaxpr_ops` gives for an image model, for a
model whose work depends on where the router sends its tokens.

Counted, per layer and sequence, forward: the five MLA projections, the
attention core by its causal half (`S^2 / 2` scores a head, twice: `q k^T`
and `p v`), the router, the routed experts by the assignments this share
holds (three products of `hidden x width` an assignment), the shared
expert, and once the head. Each product has an input-gradient and a
weight-gradient product of the same size, so a step is three times its
forward pass (the embedding's gradient needs the first block's input
gradient). Not counted: anything recomputed (the blocks' forward pass made
again for the backward pass, the attention kernel's scores made again), the
experts' products on tokens routed elsewhere (a dense masked reference
would count sixteen times the share's work), elementwise work, the sort,
and the optimiser's update.

Every entry is `{"kind", "flops", "elements"}` as `counts.roofline_seconds`
takes them: `elements` are both operands and the result, once.
"""

from __future__ import annotations

PASSES = 3          # forward, input gradient, weight gradient


def _dot(m: float, k: float, n: float, kind: str = "dot") -> dict:
    return {"kind": kind, "flops": 2.0 * m * k * n,
            "elements": m * k + k * n + m * n}


def expert_ops(arch: dict, experts_held: int, assignments: float) -> list:
    """One pass of one layer's grouped products over `assignments` rows:
    gate, up, down. The held experts' weights are read once whatever the
    rows."""
    hidden, width = arch["hidden_size"], arch["moe_intermediate_size"]
    flops = 2.0 * assignments * hidden * width
    weights = experts_held * hidden * width
    return [{"kind": "grouped", "flops": flops,
             "elements": weights + assignments * (hidden + width)}] * 3


def attention_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of one layer's causal attention core: `q k^T` and
    `p v` over the lower triangle, every head, every sequence."""
    heads = arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    scores = rows * heads * seq_len * seq_len / 2.0
    io = rows * heads * seq_len
    return [{"kind": "attention", "flops": 2.0 * scores * qk,
             "elements": io * 2 * qk},
            {"kind": "attention", "flops": 2.0 * scores * arch["v_head_dim"],
             "elements": io * 2 * arch["v_head_dim"]}]


def layer_forward_ops(arch: dict, experts_held: int, seq_len: int, rows: int,
                      assignments: float) -> list:
    tokens, hidden = rows * seq_len, arch["hidden_size"]
    heads = arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    width = arch["moe_intermediate_size"] * arch["n_shared_experts"]
    return [
        _dot(tokens, hidden, arch["q_lora_rank"]),
        _dot(tokens, arch["q_lora_rank"], heads * qk),
        _dot(tokens, hidden, arch["kv_lora_rank"]
             + arch["qk_rope_head_dim"]),
        _dot(tokens, arch["kv_lora_rank"],
             heads * (arch["qk_nope_head_dim"] + arch["v_head_dim"])),
        *attention_core_ops(arch, seq_len, rows),
        _dot(tokens, heads * arch["v_head_dim"], hidden),
        _dot(tokens, hidden, arch["n_routed_experts"]),
        *expert_ops(arch, experts_held, assignments),
        _dot(tokens, hidden, width), _dot(tokens, hidden, width),
        _dot(tokens, width, hidden),
    ]


def step_ops(*, arch: dict, layers: int, vocab_rows: int, experts_held: int,
             seq_len: int, rows: int, assignments_held) -> list:
    """Every product of one step: `assignments_held[l]` is the number of
    (token, choice) pairs layer l's router gives to an expert held here."""
    if len(assignments_held) != layers:
        raise ValueError(f"{len(assignments_held)} loads for {layers} layers")
    forward = [op for held in assignments_held for op in layer_forward_ops(
        arch, experts_held, seq_len, rows, float(held))]
    forward.append(_dot(rows * seq_len, arch["hidden_size"], vocab_rows))
    return [op for op in forward for _ in range(PASSES)]
