"""AI21-Jamba2-3B's cell through the unedited hybrid driver, at a tiny size
on the CPU: it resolves by name and states its cut from the
configuration's own `published`, its timed path agrees with the plain
reference and its line carries no routing check, each planted fault is
flagged (the recurrence's own among them), and its two readers read a
hand-made table and are restricted to it (None, never 0, on a trace
without the declared names or on another configuration's facts). Nothing
here reads a file outside the repository."""

import os

import pytest

from _tiny_jamba_lm import CELL, ROOT, context
from chipbench import run as harness
from chipbench.drivers import hybrid_lm_train as driver
from chipbench.layer_metrics import mamba1_pct, mamba1_scan_roofline_pct

READERS = {"mamba1_pct": mamba1_pct,
           "mamba1_scan_roofline_pct": mamba1_scan_roofline_pct}
ROUTING = {"expert_load_diff", "dropped_assignments"}
#: the catalog's row, as the configuration file states it
ROW = {"attn_layer_offset": 7, "attn_layer_period": 14,
       "expert_layer_offset": 1, "expert_layer_period": 2,
       "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
       "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
       "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
       "max_position_embeddings": 262144, "model_type": "jamba",
       "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
       "num_hidden_layers": 28, "num_key_value_heads": 1,
       "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
       "sliding_window": None, "tie_word_embeddings": True,
       "use_mamba_kernels": True, "vocab_size": 65536}


def test_the_cell_resolves_and_states_its_cut():
    bench, cell, config = harness.load_cell(ROOT, CELL)
    assert cell["driver"] == "hybrid_lm_train" and cell["chips"] == 1
    assert cell["batch_per_chip"] == 1 and cell["mode"] == "step"
    assert cell["traffic"] == "v8_step" and "load_diff_limit" not in cell
    for key, folder, end in (("reference", "reference", ".py"),
                             ("counts", "", ".py"), ("scopes", "", "")):
        assert os.path.isfile(os.path.join(ROOT, "chipbench", folder,
                                           config[key] + end)), key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    # the cut from the file's own `published`: every key of the row holds
    # its value but the reduced, which `published` gives back
    published = config["published"]
    assert published == {"num_hidden_layers": 28, "vocab_size": 65536,
                         "hybrid_override_pattern": "MMMMMMMAMMMMMM" * 2}
    assert {k for k, v in ROW.items() if config[k] != v} \
        == set(config["reduced"])
    assert all(ROW[k] == published[k] for k in published if k in ROW)
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["hybrid_override_pattern"]) \
        == (14, 8192, "MMMMMMMAMMMMMM")
    assert config["probe_leaf"] in config["probe_leaves"]
    assert "n_routed_experts" not in config \
        and "first_expert" not in config["recipe"]
    assert config["tie_word_embeddings"] \
        and "tie_word_embeddings" in config["arch_keys"]
    # the metrics this cell brings are restricted to it, and no other
    # configuration's reader is asked on it
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(READERS)
    assert all(m["moves"] == "train_images_per_s" for m in mine)
    for metric in bench["per_layer"]:
        if "workloads" in metric and metric["name"] not in READERS:
            assert not harness.applies(metric, CELL), metric["name"]
    # the preset states the file's numbers, at every published width; no
    # expert layer, no head of its own; the file's parameter count is the
    # state's
    import jax
    from distributed_vgg_f_tpu.config import get_config
    from distributed_vgg_f_tpu.models.registry import build_model
    cfg = get_config(config["preset"])
    recipe = driver.recipe_of(cfg, config)
    assert recipe["seq_len"] == 4096 and recipe["global_batch"] == 1
    assert driver.expert_layers(config) == []
    assert "n_routed_experts" not in driver.arch_of(config)
    shapes = jax.eval_shape(
        build_model(cfg.model).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 128), "int32"))["params"]
    assert "lm_head" not in shapes
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["parameters"] == 1_451_755_456
    flat = {driver.inputs.leaf_name(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(shapes)}
    assert set(config["probe_leaves"]) <= flat
    for tail in (*config["init"], *config["init_from_uniform"]):
        assert any(name.endswith(tail) for name in flat), tail


def test_the_timed_path_agrees_with_the_reference(tmp_path):
    line = harness.run_cell(context(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "peak_hbm_gib",
                                    "setup_s"}
    checks = line["checks"]
    assert not ROUTING & set(checks), checks
    assert checks["compiles_in_window"]["value"] == 0
    assert {"loss_gap_step3", "first_grad_gap", "change_gap",
            "first_grad_diff", "probe_grad_diff"} <= set(checks)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "chunk_reset"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    line = harness.run_cell(context(tmp_path, fault=fault))
    assert line["correct"] is False
    assert not ROUTING & set(line["checks"])
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    assert failing & {"first_grad_gap", "first_grad_diff", "change_gap",
                      "probe_grad_diff"}, line["checks"]


def _facts(scopes: dict, config=None) -> dict:
    """Facts as the driver hands them, with a hand-made table: 10 traced
    steps, 1 s of device self time."""
    if config is None:
        _, _, config = harness.load_cell(ROOT, CELL)
    lm = {"arch": driver.arch_of(config), "layers": 14, "vocab_rows": 8192,
          "experts_held": 0, "seq_len": 4096, "rows": 1,
          "assignments_held": []}
    table = {"total_s": 1.0, "phases_found": ["loss"], "scopes": {
        name: {"forward": t / 4, "backward": 3 * t / 4}
        for name, t in scopes.items()}}
    return {"lm": lm, "lm_counts": config["counts"],
            "lm_names": driver.names(config), "scopes": table,
            "traced": {"steps": 10}, "device_kind": "TPU v5 lite",
            "chips": 1}


def test_readers_on_a_hand_made_table():
    facts = _facts({"mamba1_in": 0.1, "mamba1_conv": 0.05,
                    "mamba1_xdt": 0.05, "mamba1_scan": 0.2,
                    "mamba1_gate_out": 0.05, "gqa_qkv": 0.02,
                    "gqa_core": 0.03, "gqa_out": 0.01, "mlp_dense": 0.4,
                    "lm_head": 0.09})
    assert mamba1_pct.read(facts) == pytest.approx(45.0)
    # the scan: bound by its bytes (x, dt, y a position and channel; B, C
    # a position and state; the states entering 32 chunks), 13 layers x 3
    # passes
    moved = 4096 * (5120 * 10 + 2 * 16 * 2) + 32 * 16 * 5120 * 4
    assert mamba1_scan_roofline_pct.read(facts) == pytest.approx(
        100 * (13 * 3 * moved / 819e9) * 10 / 0.2, rel=1e-6)
    assert all(0 < reader.read(facts) < 100 for reader in READERS.values())


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_none_without_the_declared_names(name):
    """Another cell's facts (Nemotron's, whose names file has an `ssm_scan`
    and a `gqa_core`); an untraced run; a trace with none of the names:
    None, never 0."""
    reader = READERS[name]
    assert reader.read({"trace_dir": None}) is None
    untraced = {**_facts({"loss": 1.0}), "scopes": None, "traced": None}
    assert reader.read(untraced) is None
    assert reader.read(_facts({"loss": 0.5, "conv1": 0.5})) is None
    for cell in ("nemotron3_nano_ep8_step", "solar_open2_ep40_step"):
        _, _, other = harness.load_cell(ROOT, cell)
        theirs = {**_facts({"mamba1_scan": 0.3, "ssm_scan": 0.3,
                            "gqa_core": 0.4}),
                  "lm_names": driver.names(other),
                  "lm_counts": other["counts"]}
        assert reader.read(theirs) is None, cell
