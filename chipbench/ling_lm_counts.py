"""Matrix products one training step of Ling-3.0-flash's cell needs
(`chipbench/drivers/hybrid_lm_train.py`, for a configuration whose file
says `counts: ling_lm_counts`), from the configuration's shapes and the
batch's routing; nothing traced, compiled or run. `hybrid_lm_counts.py`'s
scheme for a stack whose layers differ in attention and in feed-forward
(`arch["hybrid_override_pattern"]`: `D` KDA + dense, `K` KDA + experts, `L`
latent attention + experts, `A` latent + dense).

Counted, per sequence, forward:

    KDA     the q, k, v, decay-gate and output projections and the two
            head-wise ones (beta, the output gate); and the recurrence as
            its chunked WY form needs it at chunks of C = `KDA_CHUNK` = 64,
            the same whatever implements it, one entry of kind "scan": a
            chunk and head the key scores K K^T (2 C^2 d_k), the read-out's
            scores Q K^T (2 C^2 d_k), the solve (I + A)^-1 by substitution
            (C^3 / 3 multiply-adds), W = T K and U = T V (2 C^2 d_k,
            2 C^2 d_v), against the entering state W S and Q S
            (2 C d_k d_v each), the read-out's scores on U (2 C^2 d_v) and
            the chunk's state K^T U (2 C d_k d_v). A chunk's C x C blocks
            are counted whole: the mask lies inside one tile of the matrix
            unit. Its bytes are what a pass that keeps everything else on
            the chip still has to move: q, k, g in and v in, o out (heads
            x 128 a position each; g in float32 counts as two), beta.
    latent  the query projection (no compression), the two latent
            projections, the output projection, and the core by its causal
            half (`S^2 / 2` scores a head: q k^T at nope + rope = 192, p v
            at 128), with q, k in and v in, o out as bytes.
    experts the router over all experts, the routed experts by the
            assignments this share holds (three products of hidden x width
            an assignment: gate, up, down; the held experts' weights read
            once whatever the rows), the shared expert's three products.
    dense   the feed-forward's three products.
    once    the head.

Each product has an input-gradient and a weight-gradient product of the
same size, so a step is three times its forward pass. Not counted:
anything recomputed, the experts' products on tokens routed elsewhere, the
convolutions, norms and all other elementwise work, the sort, the
optimiser.

Every entry is `{"kind", "flops", "elements"}` as `counts.roofline_seconds`
takes them: `elements` are both operands and the result, once.
"""

from __future__ import annotations

PASSES = 3          # forward, input gradient, weight gradient
#: positions a chunk of the delta rule's chunked form (ops/kda.py's default)
KDA_CHUNK = 64
KINDS = {"D": ("kda", "dense"), "K": ("kda", "experts"),
         "L": ("latent", "experts"), "A": ("latent", "dense")}


def _dot(m: float, k: float, n: float, kind: str = "dot") -> dict:
    return {"kind": kind, "flops": 2.0 * m * k * n,
            "elements": m * k + k * n + m * n}


def kda_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of one KDA layer's recurrence, chunked."""
    heads, d = arch["num_attention_heads"], arch["head_dim"]
    c = min(KDA_CHUNK, seq_len)
    positions = rows * seq_len          # chunks x C
    a_position = (2.0 * c * d) * 2      # K K^T, Q K^T
    a_position += 2.0 * c * c / 3.0     # the solve
    a_position += (2.0 * c * d) * 2     # W = T K, U = T V
    a_position += (2.0 * d * d) * 2     # W S, Q S
    a_position += 2.0 * c * d           # the read-out's scores on U
    a_position += 2.0 * d * d           # K^T U
    flops = positions * heads * a_position
    # q, k, v in and o out one each; g in float32 two; beta
    elements = positions * heads * (6.0 * d + 2.0)
    return [{"kind": "scan", "flops": flops, "elements": elements}]


def attention_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of the latent layer's causal attention core:
    `q k^T` at nope + rope and `p v` at the values' size over the lower
    triangle, every head, every sequence."""
    heads = arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    scores = rows * heads * seq_len * seq_len / 2.0
    io = rows * heads * seq_len
    return [{"kind": "attention", "flops": 2.0 * scores * qk,
             "elements": io * 2 * qk},
            {"kind": "attention", "flops": 2.0 * scores * arch["v_head_dim"],
             "elements": io * 2 * arch["v_head_dim"]}]


def expert_ops(arch: dict, experts_held: int, assignments: float) -> list:
    """One pass of one layer's grouped products over `assignments` rows:
    gate, up, down."""
    hidden, width = arch["hidden_size"], arch["moe_intermediate_size"]
    return [{"kind": "grouped", "flops": 2.0 * assignments * hidden * width,
             "elements": experts_held * hidden * width
             + assignments * (hidden + width)}] * 3


def _swiglu(tokens: float, hidden: int, width: int) -> list:
    return [_dot(tokens, hidden, width), _dot(tokens, hidden, width),
            _dot(tokens, width, hidden)]


def layer_forward_ops(letter: str, arch: dict, experts_held: int,
                      seq_len: int, rows: int, assignments: float) -> list:
    tokens, hidden = rows * seq_len, arch["hidden_size"]
    heads = arch["num_attention_heads"]
    attention, ffn = KINDS[letter]
    if attention == "kda":
        inner = heads * arch["head_dim"]
        ops = [*[_dot(tokens, hidden, inner)] * 4,      # q, k, v, the gate
               *[_dot(tokens, hidden, heads)] * 2,      # beta, output gate
               *kda_core_ops(arch, seq_len, rows),
               _dot(tokens, inner, hidden)]
    else:
        qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
        ops = [_dot(tokens, hidden, heads * qk),
               _dot(tokens, hidden, arch["kv_lora_rank"]
                    + arch["qk_rope_head_dim"]),
               _dot(tokens, arch["kv_lora_rank"], heads * (
                   arch["qk_nope_head_dim"] + arch["v_head_dim"])),
               *attention_core_ops(arch, seq_len, rows),
               _dot(tokens, heads * arch["v_head_dim"], hidden)]
    if ffn == "dense":
        return ops + _swiglu(tokens, hidden, arch["intermediate_size"])
    return ops + [_dot(tokens, hidden, arch["n_routed_experts"]),
                  *expert_ops(arch, experts_held, assignments),
                  *_swiglu(tokens, hidden,
                           arch["moe_shared_expert_intermediate_size"])]


def _with_experts(pattern: str) -> list:
    return [letter for letter in pattern if KINDS[letter][1] == "experts"]


def step_ops(*, arch: dict, layers: int, vocab_rows: int, experts_held: int,
             seq_len: int, rows: int, assignments_held) -> list:
    """Every product of one step: `assignments_held[e]` is the number of
    (token, choice) pairs the e-th expert layer's router gives to an
    expert held here."""
    pattern = arch["hybrid_override_pattern"]
    if len(pattern) != layers \
            or len(_with_experts(pattern)) != len(assignments_held):
        raise ValueError(f"pattern {pattern!r}: {layers} layers, "
                         f"{len(assignments_held)} loads")
    held = iter(assignments_held)
    forward = [op for letter in pattern for op in layer_forward_ops(
        letter, arch, experts_held, seq_len, rows,
        float(next(held)) if KINDS[letter][1] == "experts" else 0.0)]
    forward.append(_dot(rows * seq_len, arch["hidden_size"], vocab_rows))
    return [op for op in forward for _ in range(PASSES)]


# ---- what the kernels' roofline readers take (layer_metrics/_hybrid_lm.py)

def _times(ops: list) -> list:
    return [op for op in ops for _ in range(PASSES)]


def kda_core_step_ops(lm: dict) -> list:
    return _times([op for letter in lm["arch"]["hybrid_override_pattern"]
                   if KINDS[letter][0] == "kda" for op in kda_core_ops(
                       lm["arch"], lm["seq_len"], lm["rows"])])


def attention_core_step_ops(lm: dict) -> list:
    return _times([op for letter in lm["arch"]["hybrid_override_pattern"]
                   if KINDS[letter][0] == "latent"
                   for op in attention_core_ops(
                       lm["arch"], lm["seq_len"], lm["rows"])])


def expert_step_ops(lm: dict) -> list:
    return _times([op for held in lm["assignments_held"]
                   for op in expert_ops(lm["arch"], lm["experts_held"],
                                        held)])
