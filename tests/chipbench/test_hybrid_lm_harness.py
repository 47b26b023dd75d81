"""The hybrid language-model cell through the harness, at a tiny size on
the CPU: it resolves by name and states its cut, its timed path agrees with
the plain reference, each planted fault is flagged (the scan's own among
them), its five readers read a hand-made table (and give None, never 0, on
a trace without the declared names), and the limits script tells the
program from the control and the faults."""

import json
import os

import pytest

from _tiny_hybrid_lm import CELL, ROOT, context, tiny
from chipbench import hybrid_lm_counts, run as harness
from chipbench.drivers import hybrid_lm_train as driver
from chipbench.layer_metrics import (gqa_core_roofline_pct,
                                     hybrid_moe_experts_roofline_pct,
                                     hybrid_moe_pct, ssm_pct,
                                     ssm_scan_roofline_pct)

READERS = {"ssm_pct": ssm_pct, "ssm_scan_roofline_pct": ssm_scan_roofline_pct,
           "gqa_core_roofline_pct": gqa_core_roofline_pct,
           "hybrid_moe_pct": hybrid_moe_pct,
           "hybrid_moe_experts_roofline_pct": hybrid_moe_experts_roofline_pct}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_the_cell_resolves_and_states_its_cut():
    bench, cell, config = harness.load_cell(ROOT, CELL)
    assert cell["driver"] == "hybrid_lm_train" and cell["chips"] == 1
    assert cell["batch_per_chip"] == 2 and cell["mode"] == "step"
    for key, folder, end in (("reference", "reference", ".py"),
                             ("counts", "", ".py"), ("scopes", "", "")):
        assert os.path.isfile(os.path.join(ROOT, "chipbench", folder,
                                           config[key] + end)), key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "hybrid_override_pattern"]
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern": PATTERN}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["hybrid_override_pattern"]) \
        == (9, 16, 16384, PATTERN[:9])
    assert config["probe_leaf"] in config["probe_leaves"]
    # the catalog's widths, untouched
    assert (config["hidden_size"], config["mamba_num_heads"],
            config["mamba_head_dim"], config["ssm_state_size"],
            config["n_groups"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"]) \
        == (2688, 64, 64, 128, 8, 32, 2, 128, 1856, 3712, 6)
    # the five metrics this cell brings are restricted to it
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(READERS)
    # the preset states the file's numbers, at every published width, and
    # the file's parameter count is the state's
    import jax
    from distributed_vgg_f_tpu.config import get_config
    from distributed_vgg_f_tpu.models.registry import build_model
    cfg = get_config(config["preset"])
    recipe = driver.recipe_of(cfg, config)
    assert recipe["seq_len"] == 8192 and recipe["global_batch"] == 2
    assert driver.arch_of(config)["n_routed_experts"] == 128
    shapes = jax.eval_shape(
        build_model(cfg.model).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 128), "int32"))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["parameters"] == 986_254_848
    flat = {driver.inputs.leaf_name(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(shapes)}
    assert set(config["probe_leaves"]) <= flat


def test_the_timed_path_agrees_with_the_reference(tmp_path):
    line = harness.run_cell(context(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "peak_hbm_gib",
                                    "setup_s"}
    checks = line["checks"]
    assert checks["dropped_assignments"]["value"] == 0
    assert checks["expert_load_diff"]["value"] == 0     # float32: no flip
    assert checks["compiles_in_window"]["value"] == 0
    assert {"loss_gap_step3", "first_grad_gap", "change_gap",
            "first_grad_diff", "probe_grad_diff"} <= set(checks)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "chunk_reset"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    line = harness.run_cell(context(tmp_path, fault=fault))
    assert line["correct"] is False
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    assert failing & {"first_grad_gap", "first_grad_diff", "change_gap",
                      "probe_grad_diff"}, line["checks"]


def test_seeded_decays_lie_in_the_published_ranges():
    """`init_from_uniform`: A = exp(A_log) in [1, 16], softplus(dt_bias)
    in [time_step_min, time_step_max], D = 1; the same draw for a group
    made alone as for the whole tree."""
    import jax
    import jax.numpy as jnp
    _, _, config = harness.load_cell(ROOT, CELL)
    shapes = {"layer_0": {"mixer": {
        name: jax.ShapeDtypeStruct((64,), jnp.float32)
        for name in ("A_log", "dt_bias", "D")}},
        "layer_1": {"mixer": {"router": jax.ShapeDtypeStruct(
            (8, 4), jnp.float32)}}}
    made = driver.make_params(shapes, driver.inputs.seed_word(2147500003),
                              config)
    mixer = made["layer_0"]["mixer"]
    a, dt = jnp.exp(mixer["A_log"]), jax.nn.softplus(mixer["dt_bias"])
    assert 1.0 <= float(a.min()) < 3 and 10 < float(a.max()) <= 16.0
    assert 0.001 <= float(dt.min()) < 0.003 and 0.03 < float(dt.max()) <= 0.1
    assert float(jnp.abs(mixer["D"] - 1).max()) == 0
    alone = driver.make_params({"layer_0": shapes["layer_0"]},
                               driver.inputs.seed_word(2147500003), config)
    assert jnp.array_equal(alone["layer_0"]["mixer"]["A_log"],
                           mixer["A_log"])
    # a leaf no rule names is `inputs.make_params`'s own draw
    plain = driver.inputs.make_params(
        shapes, driver.inputs.seed_word(2147500003), config["init"])
    assert jnp.array_equal(made["layer_1"]["mixer"]["router"],
                           plain["layer_1"]["mixer"]["router"])


def _facts(scopes: dict) -> dict:
    """Facts as the driver hands them, with a hand-made table: 10 traced
    steps, 1 s of device self time."""
    _, _, config = harness.load_cell(ROOT, CELL)
    lm = {"arch": driver.arch_of(config), "layers": 9, "vocab_rows": 16384,
          "experts_held": 16, "seq_len": 8192, "rows": 2,
          "assignments_held": [12288.0] * 4}
    table = {"total_s": 1.0, "phases_found": ["loss"], "scopes": {
        name: {"forward": t / 4, "backward": 3 * t / 4}
        for name, t in scopes.items()}}
    return {"lm": lm, "lm_counts": config["counts"],
            "lm_names": driver.names(config), "scopes": table,
            "traced": {"steps": 10}, "device_kind": "TPU v5 lite",
            "chips": 1}


def test_readers_on_a_hand_made_table():
    facts = _facts({"ssm_in": 0.06, "ssm_conv": 0.02, "ssm_scan": 0.15,
                    "ssm_gate_out": 0.02, "gqa_qkv": 0.01, "gqa_core": 0.25,
                    "gqa_out": 0.01, "moe_router": 0.01, "moe_dispatch": 0.01,
                    "moe_experts": 0.2, "moe_combine": 0.01,
                    "moe_shared": 0.02, "loss": 0.23})
    assert ssm_pct.read(facts) == pytest.approx(25.0)
    assert hybrid_moe_pct.read(facts) == pytest.approx(25.0)
    tokens = 2 * 8192
    # the core: 3 passes x 1 layer x 4 S^2 heads 128 / 2 a sequence
    core_s = 3 * 2 * (4 * 8192 ** 2 * 32 * 128 / 2) / 197e12
    assert gqa_core_roofline_pct.read(facts) == pytest.approx(
        100 * core_s * 10 / 0.25, rel=1e-6)
    # the scan: bound by its bytes (x and y, B and C, dt; two each)
    scan_bytes = 2 * tokens * (2 * 4096 + 2 * 1024 + 64)
    scan_flops = tokens * (8 * 2 * 128 * 128 + 64 * (2 * 128 * 64
                                                     + 4 * 64 * 128))
    assert scan_bytes / 819e9 > scan_flops / 197e12
    assert ssm_scan_roofline_pct.read(facts) == pytest.approx(
        100 * (3 * 4 * scan_bytes / 819e9) * 10 / 0.15, rel=1e-6)
    # the experts at 12288 assignments a layer: two products, by operations
    product_flops = 2 * 12288 * 2688 * 1856
    product_bytes = 2 * (16 * 2688 * 1856 + 12288 * (2688 + 1856))
    assert product_flops / 197e12 > product_bytes / 819e9
    assert hybrid_moe_experts_roofline_pct.read(facts) == pytest.approx(
        100 * (3 * 4 * 2 * product_flops / 197e12) * 10 / 0.2, rel=1e-6)
    assert all(0 < reader.read(facts) < 100 for reader in READERS.values())


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_none_without_the_declared_names(name):
    """Another cell's facts (the Mistral cell's among them); an untraced
    run; a trace with none of the names: None, never 0."""
    reader = READERS[name]
    assert reader.read({"trace_dir": None}) is None
    assert reader.read({"lm": {}, "scopes": {"total_s": 1.0, "scopes": {
        "moe_experts": {"forward": 1.0, "backward": 0.0}}},
        "traced": {"steps": 10}}) is None
    untraced = {**_facts({"loss": 1.0}), "scopes": None, "traced": None}
    assert reader.read(untraced) is None
    assert reader.read(_facts({"loss": 0.5, "conv1": 0.5})) is None


def test_the_mistral_cell_s_readers_stay_silent_on_this_cell_s_facts():
    """`moe_pct` and its three siblings read `lm_scopes.json`'s names from
    facts with an `lm` key: on this cell's facts they would read the
    expert share's time too, so `BENCHMARK.json` keeps them to their own
    cell, and this cell's own readers carry other names."""
    bench, _, _ = harness.load_cell(ROOT, CELL)
    for metric in bench["per_layer"]:
        if metric["name"] in ("mla_pct", "moe_pct", "mla_core_roofline_pct",
                              "moe_experts_roofline_pct"):
            assert metric["workloads"] == ["mistral_small4_ep16_step"]
            assert not harness.applies(metric, CELL)


def test_limits_readings_at_a_tiny_size():
    """`hybrid_lm_limits.readings`: the program agrees; the fp8 control and
    the three faults do not, judged by the limits as a run judges."""
    from chipbench import hybrid_lm_limits
    _, cell, config = tiny()
    rows = []
    out = hybrid_lm_limits.readings(cell, config, seeds=[11], controls=1,
                                    emit=lambda line, **kw: rows.append(
                                        json.loads(line)))
    assert out["correct"] == {
        "program": [1, 1], "control_fp8": [0, 1],
        "fault_half_batch": [0, 1], "fault_state_unchanged": [0, 1],
        "fault_chunk_reset": [0, 1]}
    assert out["worst"]["fault_state_unchanged"]["change_gap"][0] == 1.0
    assert "summary_min_max" in rows[-1]
