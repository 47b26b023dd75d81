"""The names the program gives its own work on the device.

Each name is a `jax.named_scope` at the place where the work happens.
Scopes are op metadata: they add no equation and change no compiled
instruction, but XLA carries them to every instruction's `op_name`, and a
profiler trace of the TPU stores that beside each operation. So device time
can be read by these names (`chipbench/scope_reduce.py`), and the names
survive a change to the program where XLA's own (`%fusion.512`) do not.
Forward and backward need no name: JAX wraps them as `jvp(...)` and
`transpose(jvp(...))`. Flax modules (`conv2`, `stage1_block1/bn1`) name
themselves.

The persistent compile cache's key leaves metadata out, so a change to
names alone loads the old executable, with the old names, from a cache an
earlier build has filled. Rename the jitted function (`train_step`,
`eval_step`: the module's name is in the key) or clear the cache when only
names change. `tests/test_scopes.py` holds every call site to these lists.
"""

#: Phases of the train step (train/step.py, data/augment.py) and the
#: model's input cast.
PHASES = ("finish_u8", "augment", "flip", "crop_jitter", "rand_ops", "mix",
          "cast_in", "loss", "exchange", "optimizer", "step_metrics")

#: Layers that are plain function calls in a model, not flax modules.
LAYERS = ("lrn1", "lrn2", "pool1", "pool2", "pool3", "pool4", "pool5",
          "pool_init", "gap", "embed_tokens")

#: The layers of models/mistral4.py (`embed_tokens` above is shared):
#: latent attention, the expert share, the head. A list of their own: the
#: benchmark's first names file (`chipbench/scopes.json`) is held equal to
#: `LAYERS`, and a traced run of that model's cell is reduced by a second
#: file, `chipbench/lm_scopes.json`.
LM_LAYERS = ("mla_q", "mla_kv", "mla_core", "mla_out", "moe_router",
             "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
             "lm_head")

#: The layers of models/nemotron_h.py that `LM_LAYERS` lacks (the `moe_*`
#: names, `embed_tokens` and `lm_head` are the expert share's and the
#: head's own, shared with the list above): the Mamba-2 mixer (`ssm_in`
#: its input projection, `ssm_conv`, `ssm_scan` the recurrence of
#: ops/ssd.py, `ssm_gate_out` the gated norm and the output projection)
#: and grouped-query attention. Its cell's traces are reduced by a third
#: file, `chipbench/hybrid_lm_scopes.json`.
HYBRID_LM_LAYERS = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_gate_out",
                    "gqa_qkv", "gqa_core", "gqa_out")

#: The layers of models/ling3.py that the lists above lack (`mla_*`,
#: `moe_*`, `embed_tokens` and `lm_head` are shared): Kimi Delta Attention
#: (`kda_qkv` its three projections, `kda_conv` the short convolutions and
#: the norms of q and k, `kda_gates` the decay, beta and output gates,
#: `kda_core` the recurrence of ops/kda.py, `kda_out` the head norm, the
#: gate and the output projection) and the dense feed-forward. Its cell's
#: traces are reduced by a fourth file, `chipbench/ling_lm_scopes.json`.
LING_LM_LAYERS = ("kda_qkv", "kda_conv", "kda_gates", "kda_core", "kda_out",
                  "mlp_dense")

#: The layer of models/solar_open2.py that the lists above lack (its KDA
#: layers are `LING_LM_LAYERS`' `kda_*`, its attention `HYBRID_LM_LAYERS`'
#: `gqa_*`): the gated attention's output gate, `gqa_gate`
#: (models/nemotron_h.py `GroupedQueryAttention` with `output_gate`). Its
#: cell's traces are reduced by a fifth file, `chipbench/
#: solar_lm_scopes.json`.
SOLAR_LM_LAYERS = ("gqa_gate",)

#: The layers of models/jamba2.py that the lists above lack (its attention
#: is `HYBRID_LM_LAYERS`' `gqa_*`, its MLP `LING_LM_LAYERS`' `mlp_dense`):
#: the Mamba-1 mixer (`mamba1_in` its input projection, `mamba1_conv` the
#: convolution and its silu, `mamba1_xdt` the projection to delta, B and C,
#: their norms and dt, `mamba1_scan` the recurrence of
#: ops/selective_scan.py, `mamba1_gate_out` the gate and the output
#: projection). Its cell's traces are reduced by a sixth file,
#: `chipbench/jamba_lm_scopes.json`.
JAMBA_LM_LAYERS = ("mamba1_in", "mamba1_conv", "mamba1_xdt", "mamba1_scan",
                   "mamba1_gate_out")

# ---------------------------------------------------------------------------
# Host spans of set-up: names on the telemetry ring (`telemetry.span`), not
# on the device. `chipbench/host_spans.json` is the benchmark's copy, which
# its `setup_*` readers cut the ring by (`chipbench/layer_metrics/
# _startup.py`); `tests/test_scopes.py` holds the call sites to these lists
# and `tests/chipbench/test_startup_metrics.py` the copy.
# ---------------------------------------------------------------------------

#: Category `startup` (train/trainer.py): the trainer module's import chain,
#: all of `Trainer.__init__`, its children, and `Trainer.init_state`.
STARTUP_SPANS = ("import_trainer", "trainer_init", "distributed_init",
                 "build_model", "build_optimizer", "plan_exchange",
                 "build_steps", "init_state")

#: The children among them: each lies inside `trainer_init`, on its thread.
TRAINER_INIT_CHILDREN = ("distributed_init", "build_model",
                         "build_optimizer", "plan_exchange", "build_steps")

#: Category `compile` (telemetry/compile_events.py): a span is named
#: `<stage>:<fun_name>` for one of these stages of JAX's own events.
COMPILE_SPANS = ("trace", "lower", "backend", "cache_read")
