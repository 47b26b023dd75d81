"""Matrix products one training step of AI21-Jamba2-3B's cell needs
(`chipbench/drivers/hybrid_lm_train.py`, for a configuration whose file
says `counts: jamba_lm_counts`), and what its selective scan moves, from
the configuration's shapes; nothing traced, compiled or run. The letters
are those of `arch["hybrid_override_pattern"]`: `M` Mamba-1 + MLP, `A`
attention + MLP.

Counted, per sequence, forward:

    M       the input projection (hidden x 2 d_inner), x's projection to
            delta, B and C (d_inner x (dt_rank + 2 n)), dt's projection
            (dt_rank x d_inner) and the output projection (d_inner x
            hidden)
    A       the q, k, v and output projections (heads of hidden / heads)
            and the core by its causal half (`hybrid_lm_counts.
            attention_core_ops`: 20 query heads read 1 key/value head)
    MLP     three products of hidden x intermediate_size, every layer
    once    the head (the tied embedding's transpose: one product)

Each product has an input-gradient and a weight-gradient product of the
same size, so a step is three times its forward pass. Not counted: the
selective scan (no matrix product: elementwise work, which `scan_ops`
gives its roofline reader apart), the convolution, the norms, anything
recomputed, the optimiser. Every entry is `{"kind", "flops", "elements"}`
as `counts.roofline_seconds` takes them.
"""

from __future__ import annotations

from chipbench import hybrid_lm_counts, ling_lm_counts

PASSES = 3          # forward, input gradient, weight gradient
KINDS = {"M": "mamba", "A": "attention"}
#: positions a chunk of the program's recurrence, whose entering states the
#: backward pass reads
CHUNK = 128
#: elementwise operations a position, channel and state of the recurrence:
#: dt A, its exponential, the decay times the state, (dt x) B, their sum,
#: the state times C and the sum over the states
SCAN_OPS = 7
_dot = ling_lm_counts._dot


def _attention(arch: dict) -> dict:
    return {**arch, "head_dim": arch["hidden_size"]
            // arch["num_attention_heads"]}


def attention_core_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One forward pass of the attention layer's causal core."""
    return hybrid_lm_counts.attention_core_ops(_attention(arch), seq_len,
                                               rows)


def scan_ops(arch: dict, seq_len: int, rows: int) -> list:
    """One pass of one Mamba layer's selective scan: `SCAN_OPS` elementwise
    operations a position, channel and state (no matrix product, so it is
    bound by its bytes), and as bytes x (bf16), dt and y (float32) a
    position and channel, B and C (bf16) a position and state, once, and
    the float32 state entering every chunk of `CHUNK` positions. In the
    units `counts.roofline_seconds` takes (two bytes an element)."""
    inner = arch["mamba_expand"] * arch["hidden_size"]
    n = arch["mamba_d_state"]
    positions = rows * seq_len
    moved = positions * (inner * (2 + 4 + 4) + 2 * n * 2) \
        + rows * -(-seq_len // CHUNK) * n * inner * 4
    return [{"kind": "scan", "flops": SCAN_OPS * positions * inner * n,
             "elements": moved / 2}]


def layer_forward_ops(letter: str, arch: dict, seq_len: int,
                      rows: int) -> list:
    tokens, hidden = rows * seq_len, arch["hidden_size"]
    width = arch["intermediate_size"]
    if KINDS[letter] == "mamba":
        inner = arch["mamba_expand"] * hidden
        r, n = arch["mamba_dt_rank"], arch["mamba_d_state"]
        ops = [_dot(tokens, hidden, 2 * inner), _dot(tokens, inner, r + 2 * n),
               _dot(tokens, r, inner), _dot(tokens, inner, hidden)]
    else:
        heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
        d = hidden // heads
        ops = [_dot(tokens, hidden, heads * d),
               *[_dot(tokens, hidden, kv * d)] * 2,
               *attention_core_ops(arch, seq_len, rows),
               _dot(tokens, heads * d, hidden)]
    return ops + [_dot(tokens, hidden, width), _dot(tokens, hidden, width),
                  _dot(tokens, width, hidden)]


def step_ops(*, arch: dict, layers: int, vocab_rows: int, experts_held: int,
             seq_len: int, rows: int, assignments_held) -> list:
    """Every product of one step (the stack has no experts: `experts_held`
    0 and no loads)."""
    pattern = arch["hybrid_override_pattern"]
    if len(pattern) != layers or experts_held or len(assignments_held):
        raise ValueError(f"pattern {pattern!r}: {layers} layers, "
                         f"{experts_held} experts, {assignments_held} loads")
    forward = [op for letter in pattern
               for op in layer_forward_ops(letter, arch, seq_len, rows)]
    forward.append(_dot(rows * seq_len, arch["hidden_size"], vocab_rows))
    return [op for op in forward for _ in range(PASSES)]


# ---- what the kernels' roofline readers take (layer_metrics/_hybrid_lm.py)

def scan_step_ops(lm: dict) -> list:
    """The selective scan of every Mamba layer, three passes each."""
    return [op for letter in lm["arch"]["hybrid_override_pattern"]
            if KINDS[letter] == "mamba"
            for op in scan_ops(lm["arch"], lm["seq_len"], lm["rows"])
            for _ in range(PASSES)]
