"""CPU tests of the benchmark: its arithmetic, its generator, its trace
reduction, its refusal of a machine without a chip, and its check, which
must pass a sound run and fail a run with a fault planted in the timed
path.  The cells' real sizes run only on the chip; here a tiny model of the
same family stands in."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from bench import flops, generator, harness, reference
from bench import trace as tr
from bench.reference import decoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(f[:-len(".json")] for f in
                 os.listdir(os.path.join(ROOT, "bench", "configs"))
                 if f.endswith(".json"))


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------- arithmetic
def test_prefill_flops_qwen3_by_hand():
    # per layer at S=32: projections 2*32*2048*(16+2*8)*128 + 2*32*16*128*2048
    # = 805,306,368; causal attention 4 * (32*33/2) * 16 * 128 = 4,325,376;
    # MLP 2*32*2048*(2*6144) + 2*32*6144*2048 = 2,415,919,104.  28 layers and
    # the last position's logits 2*2048*151936.
    assert decoder.prefill_flops(_config("qwen3-1.7b")["model"], 32) == \
        28 * (805_306_368 + 4_325_376 + 2_415_919_104) + 622_329_856


def test_prefill_flops_phi3v_by_hand():
    # per layer at S=576+32=608: projections 2*608*3072*(32+2*32)*96
    # + 2*608*32*96*3072 = 45,902,462,976; causal attention
    # 4 * (608*609/2) * 32 * 96 = 2,274,951,168; MLP 2*608*3072*(2*8192)
    # + 2*608*8192*3072 = 91,804,925,952.  16 layers and 2*3072*32064.
    assert decoder.prefill_flops(_config("phi3v-l16")["model"], 608) == \
        16 * (45_902_462_976 + 2_274_951_168 + 91_804_925_952) + 197_001_216


def test_search_counts_by_hand():
    st = _config("qwen3-1.7b")["store"]
    assert flops.hash_flops(st) == 2 * 5 * 64 * 64
    assert flops.cosine_flops(st, 20480) == 2 * 64 * 20480


def test_peaks_table_refuses_unknown_device():
    from bench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


# -------------------------------------------------------------- generator
def test_generator_repeats_for_a_seed_and_differs_across_seeds():
    traffic = {"stream": "cctv1", "rate_per_s": 100.0}
    a = generator.segment(traffic, 200, 2.0, 2**31 + 5, "window", 32, 151936, 0)
    b = generator.segment(traffic, 200, 2.0, 2**31 + 5, "window", 32, 151936, 0)
    c = generator.segment(traffic, 200, 2.0, 2**31 + 6, "window", 32, 151936, 0)
    np.testing.assert_array_equal(a.emb, b.emb)
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert not np.array_equal(a.emb, c.emb)
    assert not np.array_equal(a.due, c.due)
    assert a.due.min() >= 0 and a.due.max() < 2.0 and np.all(np.diff(a.due) >= 0)
    np.testing.assert_allclose(np.linalg.norm(a.emb, axis=1), 1.0, rtol=1e-5)


def test_generator_counts_are_fixed_by_rate_and_seconds():
    n, warm, warm_s = generator.window_counts(
        {"rate_per_s": 250.0, "warmup_tasks": 100}, 20.0)
    assert (n, warm, warm_s) == (5000, 100, 0.4)


# ---------------------------------------------------------- trace reduction
def _synthetic_trace():
    ms = 1e6
    host = [("bench/window", 0.0, 100 * ms), ("bench/search", 10 * ms, 20 * ms),
            ("bench/reuse_top1", 25 * ms, 4 * ms),
            ("bench/execute", 50 * ms, 30 * ms)]
    device = [("fusion.1", -5 * ms, 10 * ms),        # starts before the window
              ("reuse_top1", 26 * ms, 2 * ms),
              ("convolution.3", 55 * ms, 10 * ms),
              ("fusion.2", 60 * ms, 15 * ms),       # overlaps the one before
              ("fusion.9", 150 * ms, 5 * ms)]       # after the window
    return {"devices": [device], "host": host}


def test_trace_reduction_on_a_synthesised_trace():
    red = tr.reduce(_synthetic_trace())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [0,5] + [26,28] + [55,75] ms
    assert red["busy_s"] == pytest.approx(0.027)
    assert red["ops"]["reuse_top1"] == pytest.approx(0.002)
    assert red["ops"]["fusion.1"] == pytest.approx(0.005)
    assert "fusion.9" not in red["ops"]
    # gaps: [5,26] mid 15.5 in search; [28,55] mid 41.5 in nothing;
    # [75,100] mid 87.5 in nothing
    assert red["idle"]["search"] == pytest.approx(0.021)
    assert red["idle"]["loop"] == pytest.approx(0.027 + 0.025)
    assert tr.top(red["idle"], 1)[0][0] == "loop"


def test_trace_reduction_picks_the_innermost_span():
    ms = 1e6
    host = [("bench/window", 0.0, 10 * ms), ("bench/search", 0.0, 10 * ms),
            ("bench/reuse_top1", 2 * ms, 6 * ms)]
    red = tr.reduce({"devices": [[("op", 0.0, 1 * ms)]], "host": host})
    assert red["idle"] == {"reuse_top1": pytest.approx(0.009)}


# ------------------------------------------------------------ no chip here
def test_measurement_path_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "phi3v-l16.pandaset.over", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ------------------------------------------------------- the check, tiny
CELL = "phi3v-l16.pandaset.over"
# execution-heavy: i.i.d. frames (with images where the model takes them),
# no history
EXECUTION = {"stream": "pandaset", "threshold": 0.9, "rate_per_s": 60.0,
             "history": 0, "warmup_tasks": 10, "drain_s": 60.0}
TINY = {
    # reuse-heavy: a CCTV stream over a small history
    "qwen": ("qwen3-1.7b",
             {"stream": "cctv1", "threshold": 0.9, "rate_per_s": 60.0,
              "history": 600, "warmup_tasks": 10, "drain_s": 60.0}),
    "phi": ("phi3v-l16", EXECUTION),
}


def _tiny(cfg: dict):
    """A configuration at its reference's tiny size, with a pool of 4 images
    where the tiny model takes images.  The check compares every executed
    task, so that a fault planted in a few groups cannot hide outside a
    sample."""
    from bench import service

    cfg, arch = service.tiny_config(cfg)
    cfg["service"]["image_pool"] = 4 if cfg["model"]["frontend_tokens"] else 0
    cfg["check"].update(executed_sample=1 << 20, query_sample=64)
    return cfg, arch


def _serve(cfg: dict, arch, traffic: dict, fault=None, control=False,
           seed=2**31 + 11):
    """One run of the harness on the CPU, 1.5 s of ``traffic``."""
    stats: dict = {}
    out = harness.run(CELL, seed, 1.5, False, t_start=time.perf_counter(),
                      require_tpu=False, fault=fault, config_override=cfg,
                      traffic_override=traffic, model_override=arch,
                      stats=stats, control=control)
    return out, stats


def _tiny_run(which: str, fault=None, control=False, check=None, ref=None):
    cfg_name, traffic = TINY[which]
    cfg, arch = _tiny(_config(cfg_name))
    cfg["check"].update(check or {})
    cfg["reference"] = ref or cfg["reference"]
    return _serve(cfg, arch, traffic, fault, control)


@pytest.mark.parametrize("which,fault", [
    ("qwen", None), ("phi", None),
    ("phi", "token"),    # a served token altered where it is produced
    ("phi", "half"),     # half of each executed group left out
    ("qwen", "store"),   # store answers altered where they are produced
])
def test_check_passes_sound_runs_and_fails_planted_faults(which, fault):
    out, stats = _tiny_run(which, fault)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["correct"] is (fault is None), out["checks"]
    if which == "qwen":
        assert stats["served"].count("cs") + stats["served"].count("en") > 0
    else:
        assert stats["served"].count(None) > 0


def test_float8_control_reads_wider_gaps_than_the_program():
    # at this size the program reads a gap of 0 and the control 0.26-0.50
    # over 64 executed tasks, so the full-width model's limit does not apply
    out, stats = _tiny_run("phi", control=True, check={
        "logit_gap_limit": 0.02, "executed_sample": 64})
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]
    assert stats["checks"]["logit_gap"][0] > 3 * stats["program_gap"]
    assert stats["program_gap"] <= out["checks"]["logit_gap"]["limit"]


def _holds_the_program_layout(cfg: dict, arch) -> None:
    import jax

    from bench import service

    ref = reference.load(cfg["reference"])
    svc = service.Service(cfg, jax.random.PRNGKey(0), model_override=arch)
    want = ref.shapes(cfg["model"])
    is_shape = lambda x: isinstance(x, tuple)   # noqa: E731
    init = jax.eval_shape(svc.model.init, jax.random.PRNGKey(0))
    for tree in (svc.weights, init):
        assert jax.tree.structure(want, is_leaf=is_shape) == \
            jax.tree.structure(tree)
        assert jax.tree.leaves(want, is_leaf=is_shape) == \
            [x.shape for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_weight_layout_matches_the_program_init(name):
    _holds_the_program_layout(*_tiny(_config(name)))


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_serves_correctly_at_tiny_size(name):
    """Each configuration file at its reference's tiny size, served through
    the harness on execution-heavy traffic and held to its reference."""
    out, stats = _serve(*_tiny(_config(name)), EXECUTION, seed=2**31 + 23)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert stats["served"].count(None) > 0


TINY_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
               "d_ff", "vocab_size", "n_frontend_tokens", "norm_eps",
               "rope_theta", "dtype")


@pytest.mark.parametrize("name,numbers", [
    # the numbers ArchConfig.reduced() gave these tiny runs before their
    # references sized them, but phi3v's eps: its block's 1e-5, where
    # reduced() kept the registry's 1e-6
    ("phi3v-l16", (2, 64, 4, 2, 16, 128, 256, 8, 1e-5, 1e4, "float32")),
    ("qwen3-1.7b", (2, 64, 4, 2, 16, 128, 256, 0, 1e-6, 1e6, "float32")),
])
def test_decoder_tiny_keeps_the_size_the_tiny_runs_had(name, numbers):
    from bench import service

    full = _config(name)
    cfg, arch = service.tiny_config(full)
    assert tuple(getattr(arch, k) for k in TINY_FIELDS) == numbers
    m = cfg["model"]
    assert (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"], m["frontend_tokens"], m["rms_norm_eps"],
            m["rope_theta"], m["torch_dtype"]) == numbers
    assert full == _config(name)   # the configuration given is left as it was


@pytest.mark.parametrize("name", CONFIGS)
def test_program_arch_holds_the_fields_the_reference_expects(name):
    from bench import service

    cfg = _config(name)
    set_, expect = reference.load(cfg["reference"]).program_fields(
        cfg["model"])
    arch = service.arch_config(cfg)
    assert {k: getattr(arch, k) for k in {**set_, **expect}} == \
        {**set_, **expect}


@pytest.mark.parametrize("name,refused", [
    ("no_such_model", "known: .*'decoder'"),   # no module of that name
    ("store", "lacks \\['shapes'"),           # a module, but no model reference
    ("../decoder", "known: .*'decoder'"),      # not a module name at all
])
def test_unknown_reference_is_refused_naming_the_known_ones(name, refused):
    with pytest.raises(ValueError, match=refused):
        reference.load(name)


@pytest.fixture
def planted(monkeypatch):
    """A model reference added under a new name, with no edit to the
    harness: ``bench.reference.planted`` wraps ``decoder``, except that its
    ``last_logits`` puts the token after the true best first, by one logit,
    and its ``prefill_flops`` counts double."""
    def last_logits(model, precision="f32"):
        fwd = decoder.last_logits(model, precision)

        def favoured(w, tokens, images):
            out = fwd(w, tokens, images)
            other = (out.argmax(-1) + 1) % out.shape[-1]
            rows = np.arange(out.shape[0])
            return out.at[rows, other].set(out.max(-1) + 1.0)

        return favoured

    mod = types.ModuleType("bench.reference.planted")
    mod.shapes = decoder.shapes
    mod.make_weights = decoder.make_weights
    mod.program_fields = decoder.program_fields
    mod.tiny = decoder.tiny
    mod.last_logits = last_logits
    mod.prefill_flops = lambda model, seq: 2 * decoder.prefill_flops(model, seq)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_a_new_reference_is_built_served_and_checked(planted):
    assert reference.load("planted") is planted
    out, stats = _tiny_run("phi", ref="planted")
    assert out["attempted"] > 0 and out["failed"] == 0
    assert stats["served"].count(None) > 0
    # the served tokens are the program's own, so only the logit gap fails:
    # each lies one logit below the best that the planted reference names
    assert out["correct"] is False
    failing = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert failing == {"logit_gap"}
    assert out["checks"]["logit_gap"]["value"] >= 1.0


def test_prefill_mfu_counts_with_the_configured_reference(planted):
    cfg = _config("phi3v-l16")
    ctx = types.SimpleNamespace(
        cfg=cfg, peaks={"bf16_flops": 197e12}, prompt_len=608,
        spans=types.SimpleNamespace(calls={"execute": [0.05, 0.07, 0.06]}),
        exec_calls=[(1, 1), (3, 4), (2, 2)])
    sound = flops.prefill_mfu(ctx)
    # 6 real rows of 2.24 TFLOP over 0.18 s at 197 TFLOP/s
    assert sound == pytest.approx(100.0 * 6 * decoder.prefill_flops(
        cfg["model"], 608) / (0.18 * 197e12))
    ctx.cfg = dict(cfg, reference="planted")
    assert flops.prefill_mfu(ctx) == pytest.approx(2 * sound)


@pytest.fixture
def shrunk(monkeypatch):
    """Two model references added under new names, each ``decoder`` but for
    its ``tiny``: ``bench.reference.shrunk`` sizes the tiny model its own
    way (3 layers of hidden 32, 2 heads of 16, 1 kv head, MLP 96,
    vocabulary 128), ``bench.reference.contrary`` names 4 layers in its
    fields against the 3 of its block."""
    def tiny(model):
        m = dict(model, num_hidden_layers=3, hidden_size=32,
                 intermediate_size=96, num_attention_heads=2,
                 num_key_value_heads=1, head_dim=16, vocab_size=128,
                 frontend_tokens=0, torch_dtype="float32")
        set_, _ = decoder.program_fields(m)
        return m, {**set_, "d_model": 32, "d_ff": 96, "n_heads": 2,
                   "n_kv_heads": 1, "head_dim": 16, "vocab_size": 128,
                   "n_frontend_tokens": 0}

    def contrary(model):
        m, fields = tiny(model)
        return m, {**fields, "n_layers": 4}

    for name, fn in (("shrunk", tiny), ("contrary", contrary)):
        mod = types.ModuleType("bench.reference." + name)
        for f in reference.CONTRACT:
            setattr(mod, f, getattr(decoder, f))
        mod.tiny = fn
        monkeypatch.setitem(sys.modules, mod.__name__, mod)


def test_a_new_reference_sizes_its_own_tiny_model(shrunk):
    # the program follows the reference's numbers, not a preset of its own
    cfg, arch = _tiny(dict(_config("qwen3-1.7b"), reference="shrunk"))
    assert (arch.n_layers, arch.d_model, arch.n_heads, arch.n_kv_heads,
            arch.resolved_head_dim, arch.d_ff, arch.vocab_size) == \
        (3, 32, 2, 1, 16, 96, 128)
    _holds_the_program_layout(cfg, arch)


def test_tiny_fields_that_contradict_the_tiny_block_are_refused(shrunk):
    from bench import service

    with pytest.raises(ValueError, match="n_layers"):
        service.tiny_config(dict(_config("qwen3-1.7b"), reference="contrary"))


# ------------------------------------------------------------ the data
def test_benchmark_file_names_existing_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            name = json.load(f)["reference"]
        assert os.path.exists(os.path.join(ROOT, "bench", "reference",
                                           name + ".py"))
        reference.load(name)
    for w in bench["workloads"]:
        generator.load_traffic(w["traffic"])
    for m in bench["per_layer"]:
        harness.load_reader(m["name"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


def test_device_ops_are_named_by_module_and_kernel_tag():
    mods = [(0.0, 10.0, "jit_serve"), (20.0, 5.0, "jit_reuse_top1")]
    kernel = ('%closed_call.7 = (f32[8,1]) custom-call(f32[8,64] %a), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.op_name(kernel, 21.0, mods) == \
        "jit_reuse_top1:%closed_call.7[tpu_custom_call]"
    assert tr.op_name("%fusion.3 = bf16[8] fusion(%x)", 2.0, mods) == \
        "jit_serve:%fusion.3"
    assert tr.op_name("%copy.1 = f32[1] copy(%x)", 15.0, mods) == "%copy.1"
