"""Ling-3.0-flash's cell through the unedited hybrid driver, at a tiny size
on the CPU: it resolves by name and states its cut, its timed path agrees
with the plain reference, each planted fault is flagged (the delta rule's
own among them), its four readers read a hand-made table (and give None,
never 0, on a trace without the declared names or on another
configuration's facts), and the limits script tells the program from the
control and the faults."""

import json
import os

import pytest

from _tiny_ling_lm import CELL, ROOT, context, tiny
from chipbench import run as harness
from chipbench.drivers import hybrid_lm_train as driver
from chipbench.layer_metrics import (kda_core_roofline_pct, kda_pct,
                                     ling_moe_pct, mla192_core_roofline_pct)

READERS = {"kda_pct": kda_pct, "kda_core_roofline_pct": kda_core_roofline_pct,
           "mla192_core_roofline_pct": mla192_core_roofline_pct,
           "ling_moe_pct": ling_moe_pct}
PATTERN = "DD" + "KKKLKK" * 6 + "KKKL"


def test_the_cell_resolves_and_states_its_cut():
    bench, cell, config = harness.load_cell(ROOT, CELL)
    assert cell["driver"] == "hybrid_lm_train" and cell["chips"] == 1
    assert cell["batch_per_chip"] == 2 and cell["mode"] == "step"
    assert cell["traffic"] == "ep64_step"
    for key, folder, end in (("reference", "reference", ".py"),
                             ("counts", "", ".py"), ("scopes", "", "")):
        assert os.path.isfile(os.path.join(ROOT, "chipbench", folder,
                                           config[key] + end)), key
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "hybrid_override_pattern"]
    assert config["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "n_routed_experts": 512, "vocab_size": 157184,
        "hybrid_override_pattern": PATTERN}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_experts"], config["n_routed_experts"],
            config["vocab_size"], config["hybrid_override_pattern"]) \
        == (7, 1, 8, 8, 19648, "DKKKKLK")
    assert config["probe_leaf"] in config["probe_leaves"]
    # every number of the catalog's row under its own key, but the reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash-VL")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config[k] != v} \
            == set(config["reduced"]) - {"hybrid_override_pattern"}
    # the widths, untouched
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["head_dim"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["n_group"],
            config["topk_group"]) \
        == (2560, 6144, 32, 128, 512, 128, 64, 128, 768, 768, 8, 8, 4)
    # the four metrics this cell brings are restricted to it, and no
    # other configuration's reader is asked on it
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(READERS)
    assert all(CELL not in m.get("workloads", [CELL]) or m in mine
               or "workloads" not in m for m in bench["per_layer"])
    # the preset states the file's numbers, at every published width, and
    # the file's parameter count is the state's
    import jax
    from distributed_vgg_f_tpu.config import get_config
    from distributed_vgg_f_tpu.models import ling3
    from distributed_vgg_f_tpu.models.registry import build_model
    cfg = get_config(config["preset"])
    recipe = driver.recipe_of(cfg, config)
    assert recipe["seq_len"] == 8192 and recipe["global_batch"] == 2
    assert driver.arch_of(config)["n_routed_experts"] == 512
    assert ling3.pattern_of(42, 6, 2) == PATTERN
    shapes = jax.eval_shape(
        build_model(cfg.model).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 128), "int32"))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["parameters"] == 821_954_496
    flat = {driver.inputs.leaf_name(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(shapes)}
    assert set(config["probe_leaves"]) <= flat
    # every `init` rule names a leaf the model has
    for tail in (*config["init"], *config["init_from_uniform"]):
        assert any(name.endswith(tail) for name in flat), tail


def test_the_timed_path_agrees_with_the_reference(tmp_path):
    line = harness.run_cell(context(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "peak_hbm_gib",
                                    "setup_s"}
    checks = line["checks"]
    assert checks["dropped_assignments"]["value"] == 0
    # float32 on both sides: a near-tie that falls the other way moves a
    # few of the share's ~1,400 assignments (`_tiny_ling_lm.LIMITS`)
    assert checks["expert_load_diff"]["value"] <= 0.005
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
    """`init_from_uniform`: exp(A_log) in [1, 16], softplus(dt_bias) in
    [0.001, 0.1]; the seeded gate then decays a channel by something
    between nothing and its bound."""
    import jax
    import jax.numpy as jnp
    _, _, config = harness.load_cell(ROOT, CELL)
    shapes = {"layer_0": {"attn": {
        "A_log": jax.ShapeDtypeStruct((32,), jnp.float32),
        "dt_bias": jax.ShapeDtypeStruct((4096,), jnp.float32)}}}
    attn = driver.make_params(shapes, driver.inputs.seed_word(2147500003),
                              config)["layer_0"]["attn"]
    a, dt = jnp.exp(attn["A_log"]), jax.nn.softplus(attn["dt_bias"])
    assert 1.0 <= float(a.min()) < 3 and 10 < float(a.max()) <= 16.0
    assert 0.001 <= float(dt.min()) < 0.002 and 0.05 < float(dt.max()) <= 0.1
    f = jax.random.normal(jax.random.key(0), (256, 32, 128))
    g = -5 * jax.nn.sigmoid(a[:, None] * (f + attn["dt_bias"].reshape(
        32, 128)))
    # exp(A_log) up to 16 makes the sigmoid steep: a channel either hardly
    # decays (f + dt_bias < 0, nearly always) or sits at the bound
    assert -5 <= float(g.min()) < -4 and -0.01 < float(g.max()) <= 0
    assert 0.001 < float(jnp.mean(g < -0.5)) < 0.05


def _facts(scopes: dict) -> dict:
    """Facts as the driver hands them, with a hand-made table: 10 traced
    steps, 1 s of device self time."""
    _, _, config = harness.load_cell(ROOT, CELL)
    lm = {"arch": driver.arch_of(config), "layers": 7, "vocab_rows": 19648,
          "experts_held": 8, "seq_len": 8192, "rows": 2,
          "assignments_held": [2048.0] * 6}
    table = {"total_s": 1.0, "phases_found": ["loss"], "scopes": {
        name: {"forward": t / 4, "backward": 3 * t / 4}
        for name, t in scopes.items()}}
    return {"lm": lm, "lm_counts": config["counts"],
            "lm_names": driver.names(config), "scopes": table,
            "traced": {"steps": 10}, "device_kind": "TPU v5 lite",
            "chips": 1}


def test_readers_on_a_hand_made_table():
    facts = _facts({"kda_qkv": 0.1, "kda_conv": 0.05, "kda_gates": 0.05,
                    "kda_core": 0.25, "kda_out": 0.05, "mla_q": 0.01,
                    "mla_core": 0.25, "moe_router": 0.02,
                    "moe_dispatch": 0.02, "moe_experts": 0.1,
                    "moe_combine": 0.02, "moe_shared": 0.04,
                    "mlp_dense": 0.04})
    assert kda_pct.read(facts) == pytest.approx(50.0)
    assert ling_moe_pct.read(facts) == pytest.approx(20.0)
    tokens = 2 * 8192
    # the latent core: 3 passes x 1 layer x 2 S^2 heads (192 + 128) / 2 a
    # sequence, bound by its operations
    core_s = 3 * 2 * (2 * 8192 ** 2 * 32 * (192 + 128) / 2) / 197e12
    assert mla192_core_roofline_pct.read(facts) == pytest.approx(
        100 * core_s * 10 / 0.25, rel=1e-6)
    # the delta rule: bound by its bytes (q, k, v, o one each, g two, beta)
    core_bytes = 2 * tokens * 32 * (6 * 128 + 2)
    assert kda_core_roofline_pct.read(facts) == pytest.approx(
        100 * (3 * 6 * core_bytes / 819e9) * 10 / 0.25, rel=1e-6)
    assert all(0 < reader.read(facts) < 100 for reader in READERS.values())


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_none_without_the_declared_names(name):
    """Another cell's facts (the two other language cells' among them); an
    untraced run; a trace with none of the names: None, never 0."""
    reader = READERS[name]
    assert reader.read({"trace_dir": None}) is None
    assert reader.read({"lm": {}, "scopes": {"total_s": 1.0, "scopes": {
        "moe_experts": {"forward": 1.0, "backward": 0.0}}},
        "traced": {"steps": 10}}) is None
    untraced = {**_facts({"loss": 1.0}), "scopes": None, "traced": None}
    assert reader.read(untraced) is None
    assert reader.read(_facts({"loss": 0.5, "conv1": 0.5})) is None
    # the Nemotron cell's facts: its names file has no `kda` group, though
    # its trace holds the expert share's names
    _, _, other = harness.load_cell(ROOT, "nemotron3_nano_ep8_step")
    theirs = {**_facts({"moe_experts": 0.5, "gqa_core": 0.5}),
              "lm_names": driver.names(other), "lm_counts": other["counts"]}
    assert reader.read(theirs) is None


def test_the_other_language_cells_readers_stay_off_this_cell():
    bench, _, _ = harness.load_cell(ROOT, CELL)
    for metric in bench["per_layer"]:
        if "workloads" in metric and metric["name"] not in READERS:
            assert not harness.applies(metric, CELL), metric["name"]
    # and the five without a list read through the facts the driver builds
    assert sum("workloads" not in m for m in bench["per_layer"]) == 5


def test_limits_readings_at_a_tiny_size():
    """`hybrid_lm_limits.readings`, unedited, on this configuration: the
    program agrees; the fp8 control and the three faults do not, judged by
    the limits as a run judges."""
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
