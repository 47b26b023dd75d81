"""Matrix products one training step of Solar-Open2's cell needs
(`chipbench/drivers/hybrid_lm_train.py`, for a configuration whose file
says `counts: solar_lm_counts`), from the configuration's shapes and the
batch's routing; nothing traced, compiled or run. `ling_lm_counts.py`'s
scheme for the letters `G` (gated grouped-query attention + experts) and
`K` (Kimi Delta Attention + experts) of `arch["hybrid_override_pattern"]`.

Counted, per sequence, forward:

    KDA     the q, k, v and output projections; the decay gate's and the
            output gate's two low-rank products each (hidden x rank, rank
            x heads d: `kda_use_full_proj` false) or one whole product;
            beta's; and the recurrence as `ling_lm_counts.kda_core_ops`
            counts it at the linear layers' heads and head size (the
            chunked WY form at chunks of 64, the same whatever implements
            it: the pairwise diagonal sub-blocks of the unbounded gate are
            work of the implementation, not of the recurrence), bound by
            its bytes.
    GQA     the q, k, v, gate and output projections and the core by its
            causal half (`hybrid_lm_counts.attention_core_ops`: 64 query
            heads read 8 key/value heads, their bytes by the heads there
            are).
    experts the router over all experts, the routed experts by the
            assignments this share holds (three products of hidden x width
            an assignment, the held experts' weights read once), the
            shared experts' three products.
    once    the head.

Each product has an input-gradient and a weight-gradient product of the
same size, so a step is three times its forward pass. Not counted:
anything recomputed, the experts' products on tokens routed elsewhere, the
convolutions, norms and all other elementwise work, the sort, the
optimiser. Every entry is `{"kind", "flops", "elements"}` as
`counts.roofline_seconds` takes them.
"""

from __future__ import annotations

from chipbench import hybrid_lm_counts, ling_lm_counts

PASSES = 3          # forward, input gradient, weight gradient
KINDS = {"G": "gqa", "K": "kda"}
_dot = ling_lm_counts._dot


def _linear(arch: dict) -> dict:
    """The delta-rule layers' heads as `ling_lm_counts` reads them."""
    linear = arch["linear_attn_config"]
    return {"num_attention_heads": linear["num_heads"],
            "head_dim": linear["head_dim"]}


def kda_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of one KDA layer's recurrence, chunked."""
    return ling_lm_counts.kda_core_ops(_linear(arch), seq_len, rows)


def attention_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of one GQA layer's causal core."""
    return hybrid_lm_counts.attention_core_ops(arch, seq_len, rows)


def _gate(tokens: float, hidden: int, out: int, arch: dict) -> list:
    """A gate's projection, through its rank unless it is whole."""
    if arch["kda_use_full_proj"]:
        return [_dot(tokens, hidden, out)]
    rank = arch["linear_attn_config"]["head_dim"]
    return [_dot(tokens, hidden, rank), _dot(tokens, rank, out)]


def layer_forward_ops(letter: str, arch: dict, experts_held: int,
                      seq_len: int, rows: int, assignments: float) -> list:
    tokens, hidden = rows * seq_len, arch["hidden_size"]
    if KINDS[letter] == "kda":
        linear = _linear(arch)
        heads = linear["num_attention_heads"]
        inner = heads * linear["head_dim"]
        ops = [*[_dot(tokens, hidden, inner)] * 3,      # q, k, v
               *_gate(tokens, hidden, inner, arch),     # the decay gate
               *_gate(tokens, hidden, inner, arch),     # the output gate
               _dot(tokens, hidden, heads),             # beta
               *kda_core_ops(arch, seq_len, rows),
               _dot(tokens, inner, hidden)]
    else:
        heads, kv, d = (arch["num_attention_heads"],
                        arch["num_key_value_heads"], arch["head_dim"])
        ops = [_dot(tokens, hidden, heads * d),
               *[_dot(tokens, hidden, kv * d)] * 2,
               *attention_core_ops(arch, seq_len, rows),
               *([_dot(tokens, hidden, heads * d)] if arch["use_gqa_gate"]
                 else []),
               _dot(tokens, heads * d, hidden)]
    width = arch["moe_intermediate_size"]
    shared = width * arch["n_shared_experts"]
    return ops + [_dot(tokens, hidden, arch["n_routed_experts"]),
                  *ling_lm_counts.expert_ops(arch, experts_held, assignments),
                  _dot(tokens, hidden, shared), _dot(tokens, hidden, shared),
                  _dot(tokens, shared, hidden)]


def step_ops(*, arch: dict, layers: int, vocab_rows: int, experts_held: int,
             seq_len: int, rows: int, assignments_held) -> list:
    """Every product of one step: `assignments_held[e]` is the number of
    (token, choice) pairs the e-th layer's router gives to an expert held
    here (every layer has experts)."""
    pattern = arch["hybrid_override_pattern"]
    if len(pattern) != layers or len(assignments_held) != layers:
        raise ValueError(f"pattern {pattern!r}: {layers} layers, "
                         f"{len(assignments_held)} loads")
    forward = [op for letter, held in zip(pattern, assignments_held)
               for op in layer_forward_ops(letter, arch, experts_held,
                                           seq_len, rows, float(held))]
    forward.append(_dot(rows * seq_len, arch["hidden_size"], vocab_rows))
    return [op for op in forward for _ in range(PASSES)]


# ---- what the kernels' roofline readers take (layer_metrics/_hybrid_lm.py)

def _times(ops: list) -> list:
    return [op for op in ops for _ in range(PASSES)]


def _of_kind(lm: dict, kind: str, ops_of) -> list:
    return _times([op for letter in lm["arch"]["hybrid_override_pattern"]
                   if KINDS[letter] == kind
                   for op in ops_of(lm["arch"], lm["seq_len"], lm["rows"])])


def kda_core_step_ops(lm: dict) -> list:
    return _of_kind(lm, "kda", kda_core_ops)


def attention_core_step_ops(lm: dict) -> list:
    return _of_kind(lm, "gqa", attention_core_ops)
