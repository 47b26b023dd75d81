"""A language-model cell with no routed experts, at what a CPU test holds:
the preset `ling3_flash_tiny` with every layer's feed-forward dense
(`first_k_dense_replace` 7, so `DDDDDAD`: Kimi-delta layers and one latent
layer, a SwiGLU of 96 in each, as Jamba's layers have), hidden 64, 4 heads
of 16, sequences of 128, vocabulary 256, two sequences, float32 compute.
Its configuration names no expert key and its recipe no `first_expert`.

The program's stacks give the expert layers' counts as `jnp.stack` of a
list that is empty here, so `expert_free_program()` gives the step the
empty table, (0 expert layers, 1), that a model without experts hands the
train step; everything else is the preset's own model."""

import contextlib
import copy
import os
import time

from chipbench import run as harness

ROOT = harness.ROOT
CELL = "ling3_flash_ep64_step"
PATTERN = "DDDDDAD"
#: the preset's rate, 0.01 with momentum 0.9, overshoots on this stack: its
#: loss barely falls, and the step map grows the two sides' float32
#: rounding a hundredfold a step (loss gaps 8e-8, 2e-5, 1.3e-3 over the
#: three steps of seed 23; change_gap 0.29 and 0.35 on seeds 19 and 23 of
#: nine). At 0.001 the loss falls 0.26 a step and seed 23 reads 3e-6 and
#: 2e-4. The rate is the same on both sides, so it cuts no check short.
BASE_LR = 0.001
#: float32 on both sides and no router. Twelve seeds at BASE_LR read at
#: most 2.7e-5 in a step's loss, 2.0e-5 in the first gradient's norms,
#: 1.4e-5 and 3.2e-6 in its leaves and probe, and 6.2e-3 in a small
#: leaf's change (a norm's scale); each planted fault reads 0.5 or more.
LIMITS = {"loss_gap": 2e-4, "first_grad_gap": 1e-3, "change_gap": 5e-2,
          "first_grad_diff": 1e-3, "probe_grad_diff": 1e-3}
EXPERT_KEYS = ("num_experts_per_tok", "n_group", "topk_group",
               "moe_intermediate_size", "moe_shared_expert_intermediate_size",
               "routed_scaling_factor")


@contextlib.contextmanager
def expert_free_program():
    """`LingLM.hidden` with the empty counts table in place of the stack
    of no counts."""
    import jax
    import jax.numpy as jnp

    from distributed_vgg_f_tpu.models import ling3

    def hidden(self, tokens):
        with jax.named_scope("embed_tokens"):
            x = self.embed(tokens)
        for block in self.blocks:
            x, count = block(x)
            assert count is None, "an expert layer in an expert-free stack"
        return self.norm(x), jnp.zeros((0, 1), jnp.int32)

    real = ling3.LingLM.hidden
    ling3.LingLM.hidden = hidden
    try:
        yield
    finally:
        ling3.LingLM.hidden = real


def tiny(root: str = ROOT):
    from distributed_vgg_f_tpu.config import get_config
    bench, cell, config = harness.load_cell(root, CELL)
    config = copy.deepcopy(config)
    overrides = {"model.extra.first_k_dense_replace": len(PATTERN),
                 "model.extra.hybrid_override_pattern": PATTERN,
                 "optim.base_lr": BASE_LR, "train.log_every": 5}
    extra = {**get_config("ling3_flash_tiny").model.extra,
             "first_k_dense_replace": len(PATTERN),
             "hybrid_override_pattern": PATTERN}
    config["arch_keys"] = [k for k in config["arch_keys"]
                           if k not in EXPERT_KEYS]
    for key in EXPERT_KEYS + ("n_routed_experts", "num_experts"):
        config.pop(key, None)
        config["published"].pop(key, None)
    config.update({k: extra[k] for k in config["arch_keys"]})
    config.update(
        preset="ling3_flash_tiny", num_hidden_layers=len(PATTERN),
        vocab_size=256, reference_block_rows=32, overrides=overrides,
        # the published ranges for the decays; everything else by default
        init={k: v for k, v in config["init"].items()
              if k in ("attn/A_log", "attn/dt_bias")},
        probe_leaves=["lm_head/kernel", "layer_0/attn/A_log",
                      "layer_2/attn/dt_bias", "layer_3/mlp/down_proj/kernel",
                      "layer_5/attn/kv_b_proj/kernel"])
    del config["recipe"]["first_expert"]
    config["recipe"].update(seq_len=128, compute_dtype="float32",
                            reference_batch=2, base_lr=BASE_LR)
    cell = {k: v for k, v in cell.items() if k != "load_diff_limit"}
    cell.update(batch_per_chip=2, limits=dict(LIMITS))
    return bench, cell, config


def context(tmp_path, *, seed=3, fault=None, seconds=0.3):
    bench, cell, config = tiny()
    return harness.Context(
        root=ROOT, bench=bench, cell=cell, config=config, seed=seed,
        seconds=seconds, trace=False, t0=time.perf_counter(),
        out_dir=os.path.join(str(tmp_path), "out"), fault=fault)
