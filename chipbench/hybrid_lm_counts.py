"""Matrix products one training step of a hybrid language-model cell needs
(`chipbench/drivers/hybrid_lm_train.py`, for a configuration whose file
says `counts: hybrid_lm_counts`), from the configuration's shapes and the
batch's routing; nothing traced, compiled or run. `lm_counts.py`'s scheme
for a stack whose layers differ in kind (`arch["hybrid_override_pattern"]`:
`M` Mamba-2, `E` experts, `*` grouped-query attention).

Counted, per sequence, forward:

    M   the input and output projections; and the scan as the chunked form
        of the recurrence needs it at the configuration's `chunk_size` Q,
        one entry of kind "scan": per chunk and group C B^T (2 Q^2 n), per
        chunk and head the masked product with x (2 Q^2 p), the chunk's
        state (2 Q p n) and its read-out (2 Q n p). A chunk's Q x Q block
        is counted whole: the mask lies inside one tile of the matrix unit.
        Its bytes are what a pass that keeps everything else on the chip
        still has to move: x in and y out (heads x p a position each), B
        and C (groups x n each), dt (heads).
    E   the router over all experts, the routed experts by the assignments
        this share holds (TWO products of hidden x width an assignment: up,
        down; the held experts' weights read once whatever the rows), the
        shared expert's two products.
    *   the four projections (k and v at the key heads' width) and the core
        by its causal half (`S^2 / 2` scores a query head, twice), with the
        bytes of the key and value heads there are, not of one a query head.
    once the head.

Each product has an input-gradient and a weight-gradient product of the
same size, so a step is three times its forward pass. Not counted:
anything recomputed, the experts' products on tokens routed elsewhere, the
convolution and all other elementwise work, the sort, the optimiser.

Every entry is `{"kind", "flops", "elements"}` as `counts.roofline_seconds`
takes them: `elements` are both operands and the result, once.
"""

from __future__ import annotations

PASSES = 3          # forward, input gradient, weight gradient


def _dot(m: float, k: float, n: float, kind: str = "dot") -> dict:
    return {"kind": kind, "flops": 2.0 * m * k * n,
            "elements": m * k + k * n + m * n}


def scan_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of one Mamba layer's recurrence, chunked."""
    heads, p, groups, n = (arch["mamba_num_heads"], arch["mamba_head_dim"],
                           arch["n_groups"], arch["ssm_state_size"])
    q = min(arch["chunk_size"], seq_len)
    positions = rows * seq_len          # chunks x Q
    flops = positions * (groups * 2.0 * q * n
                         + heads * (2.0 * q * p + 4.0 * p * n))
    elements = positions * (2.0 * heads * p + 2.0 * groups * n + heads)
    return [{"kind": "scan", "flops": flops, "elements": elements}]


def attention_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of one layer's causal attention core: `q k^T` and
    `p v` over the lower triangle, every query head, every sequence."""
    heads, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                    arch["head_dim"])
    flops = 2.0 * rows * heads * seq_len * seq_len / 2.0 * d
    io = rows * seq_len * d * (heads + kv)
    return [{"kind": "attention", "flops": flops, "elements": io}] * 2


def expert_ops(arch: dict, experts_held: int, assignments: float) -> list:
    """One pass of one layer's grouped products over `assignments` rows:
    up, down."""
    hidden, width = arch["hidden_size"], arch["moe_intermediate_size"]
    return [{"kind": "grouped", "flops": 2.0 * assignments * hidden * width,
             "elements": experts_held * hidden * width
             + assignments * (hidden + width)}] * 2


def layer_forward_ops(letter: str, arch: dict, experts_held: int,
                      seq_len: int, rows: int, assignments: float) -> list:
    tokens, hidden = rows * seq_len, arch["hidden_size"]
    if letter == "M":
        inner = arch["mamba_num_heads"] * arch["mamba_head_dim"]
        width = 2 * inner + 2 * arch["n_groups"] * arch["ssm_state_size"] \
            + arch["mamba_num_heads"]
        return [_dot(tokens, hidden, width),
                *scan_ops(arch, seq_len, rows),
                _dot(tokens, inner, hidden)]
    if letter == "*":
        d = arch["head_dim"]
        q, kv = arch["num_attention_heads"] * d, \
            arch["num_key_value_heads"] * d
        return [_dot(tokens, hidden, q), _dot(tokens, hidden, kv),
                _dot(tokens, hidden, kv),
                *attention_core_ops(arch, seq_len, rows),
                _dot(tokens, q, hidden)]
    if letter == "E":
        shared = arch["moe_shared_expert_intermediate_size"]
        return [_dot(tokens, hidden, arch["n_routed_experts"]),
                *expert_ops(arch, experts_held, assignments),
                _dot(tokens, hidden, shared), _dot(tokens, shared, hidden)]
    raise ValueError(f"no kind of layer {letter!r}")


def step_ops(*, arch: dict, layers: int, vocab_rows: int, experts_held: int,
             seq_len: int, rows: int, assignments_held) -> list:
    """Every product of one step: `assignments_held[e]` is the number of
    (token, choice) pairs the e-th expert layer's router gives to an
    expert held here."""
    pattern = arch["hybrid_override_pattern"]
    if len(pattern) != layers or pattern.count("E") != len(assignments_held):
        raise ValueError(f"pattern {pattern!r}: {layers} layers, "
                         f"{len(assignments_held)} loads")
    held = iter(assignments_held)
    forward = [op for letter in pattern for op in layer_forward_ops(
        letter, arch, experts_held, seq_len, rows,
        float(next(held)) if letter == "E" else 0.0)]
    forward.append(_dot(rows * seq_len, arch["hidden_size"], vocab_rows))
    return [op for op in forward for _ in range(PASSES)]


# ---- what the kernels' roofline readers take (layer_metrics/_hybrid_lm.py)

def _times(ops: list) -> list:
    return [op for op in ops for _ in range(PASSES)]


def scan_step_ops(lm: dict) -> list:
    return _times([op for letter in lm["arch"]["hybrid_override_pattern"]
                   if letter == "M"
                   for op in scan_ops(lm["arch"], lm["seq_len"], lm["rows"])])


def attention_core_step_ops(lm: dict) -> list:
    return _times([op for letter in lm["arch"]["hybrid_override_pattern"]
                   if letter == "*" for op in attention_core_ops(
                       lm["arch"], lm["seq_len"], lm["rows"])])


def expert_step_ops(lm: dict) -> list:
    return _times([op for held in lm["assignments_held"]
                   for op in expert_ops(lm["arch"], lm["experts_held"],
                                        held)])
