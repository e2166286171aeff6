"""falcon-h1-34b in the benchmark: the flash prefill and hybrid step work
against hand counts at two shapes each, the configuration file against the
program's registry entry and its own published keys, the reference's
weight layout against the program's, the new entries of BENCHMARK.json,
the readers on a made-up window, and the cell run end to end at a CPU
size."""
import dataclasses
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from chipbench_testlib import ROOT, harness


def mod(rel):
    return harness().module(rel)


def conf():
    return harness().read_json("configs", "falcon-h1-34b.json")


def test_flash_prefill_hand_count():
    k = mod("kernels/flash_prefill.py")
    # one chunk in its own bucket (256 rows = 256 queries, nothing before
    # it): b=2, h=4, d=8, each query 0 + 257/2 pairs at 4 d FLOPs
    assert k.flops(2, 4, 256, 8, 256) == 4 * 8 * 2 * 4 * 256 * 128.5 \
        == 8421376
    # q in and o out 2 x 16384 x 2 B, K and V over 256 rows of 2 heads
    assert k.bytes_moved(2, 4, 2, 256, 8, 256) == 65536 + 32768
    # a bucket of 8 chunks (2048 rows): at least 1024 - 256 = 768 keys
    # before the chunk, so 768 + 128.5 pairs a query
    assert k.flops(1, 4, 256, 8, 2048) == 32768 * 896.5 == 29376512
    assert k.bytes_moved(1, 4, 1, 256, 8, 2048) == 32768 + 2 * 1024 * 8 * 2
    key = ("jit_chunk_step/%flash_prefill_kv2048.10 = bf16[1,4,256,8] "
           "custom-call")
    assert k.kv_rows(key) == 2048
    assert k.work({"attn": {"n_kv_heads": 1}}, key) == (29376512, 65536)
    assert re.search(k.PATTERN, key)
    assert not re.search(k.PATTERN, "jit_decode_n/%attn_core.7 = "
                         "(f32[2,4,8,5,128]) custom-call")


def test_hybrid_work_hand_count_at_published_widths():
    w = mod("work_hybrid.py")
    m = conf()["model"]
    # a layer: attention 5120 x 28 x 128 + 2560 x 5120 = 31457280, Mamba
    # 5120 x (4096 + 5120 + 32) + 4096 x 5120 = 68321280, MLP
    # 3 x 5120 x 21504 = 330301440
    assert w.matmul_weights(m) == m["n_layers"] * 430080000
    # plus the untied head (5120 x 261120) and the small leaves: per layer
    # conv taps and bias 5120 x 5, A_log, D, dt_bias 3 x 32, the gated
    # norm 4096, two norms 2 x 5120; final norm.  The embedding table is
    # not read whole: a chunk gathers its tokens' rows
    small = m["n_layers"] * (5120 * 5 + 96 + 4096 + 10240) + 5120
    assert w.weight_bytes(m) == 2 * (m["n_layers"] * 430080000
                                     + 261120 * 5120 + small)
    tree = jax.eval_shape(lambda: mod(conf()["reference"]).init_weights(
        m, jax.random.PRNGKey(0)))
    assert w.weight_bytes(m) == sum(
        int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(tree)) \
        - 2 * int(np.prod(tree["embed"].shape))
    # 512 tokens in 2 programs of 256: the weights twice, 512 embedding
    # rows of 5120, 2 state round trips a row, 512 tokens' keys and values
    z = w.sizes(m)
    _, b = w.prefill_work(m, 512, 2, 256, 0.0)
    assert b == (2 * w.weight_bytes(m) + 512 * 5120 * 2
                 + 2 * 2 * w.row_state_bytes(z)
                 + 512 * m["n_layers"] * 2 * 4 * 128 * 2)


def test_hybrid_work_hand_count_at_a_cpu_size():
    w = mod("work_hybrid.py")
    m = _tiny_model()
    # attention 64 x 14 x 16 + 10 x 16 x 64, Mamba 64 x (64 + 128 + 4)
    # + 64 x 64, MLP 3 x 64 x 96: 59648 a layer, 2 layers
    assert w.matmul_weights(m) == 2 * (24576 + 16640 + 18432) == 119296
    # 300 tokens through 2 layers fill the cheapest calls first: 512
    # row-tokens of a one-chunk bucket at 128.5 pairs, then 88 of a
    # 2048-row bucket at 896.5
    calls = [(4, 2, 256, 2048), (2, 1, 256, 256)]
    assert w.attention_pairs(calls, 300, 2) == 512 * 128.5 + 88 * 896.5
    z = w.sizes(m)
    f, b = w.prefill_work(m, 512, 3, 256, 1000.0)
    assert f == 512 * (2 * 119296 + 2 * mod("kernels/ssd_scan.py").flops(
        1, 16, 4, 16, 16, 2, 16) / 16) + 4 * 16 * 10 * 1000.0
    assert b == (3 * w.weight_bytes(m) + 512 * 64 * 2
                 + 2 * 2 * w.row_state_bytes(z) + 512 * 2 * 2 * 2 * 16 * 2)


def test_config_file_states_what_runs():
    """The file's published keys (at their source's names), its ``model``
    section and the program's registry entry agree on every width; only
    the depth is cut, and ``reduced`` says so."""
    c = conf()
    m = c["model"]
    cfg = harness().program_config(c)
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == m["n_layers"] == cfg.n_layers == 4
    from repro.core.registry import get
    full = get("falcon-h1-34b")
    assert full.n_layers == 72
    # served in its compute dtype, as the file states
    assert cfg == dataclasses.replace(full, n_layers=4,
                                      param_dtype="bfloat16")
    a, s, u = cfg.attn, cfg.ssm, cfg.mup
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"]) == \
        (cfg.d_model, cfg.d_ff, cfg.vocab_size)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["rope_theta"]) == \
        (a.n_heads, a.n_kv_heads, a.head_dim, a.rope_theta)
    assert (c["mamba_d_ssm"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_d_conv"], c["mamba_chunk_size"],
            c["mamba_expand"]) == \
        (s.d_inner(cfg.d_model), s.headdim, s.d_state, s.n_groups,
         s.conv_kernel, s.chunk, s.expand)
    assert c["mamba_n_heads"] == s.n_ssm_heads(cfg.d_model)
    assert (c["embedding_multiplier"], c["lm_head_multiplier"],
            c["attention_in_multiplier"], c["attention_out_multiplier"],
            c["key_multiplier"], c["ssm_in_multiplier"],
            c["ssm_out_multiplier"]) == \
        (u.embedding, u.lm_head, u.attn_in, u.attn_out, u.key, u.ssm_in,
         u.ssm_out)
    assert tuple(c["ssm_multipliers"]) == u.ssm
    assert tuple(c["mlp_multipliers"]) == u.mlp
    assert c["tie_word_embeddings"] is cfg.tie_embeddings is False
    assert c["rms_norm_eps"] == cfg.norm_eps
    assert c["mamba_rms_norm"] and not c["mamba_norm_before_gate"]


def test_reference_layout_is_the_programs():
    """At every published width and a depth of 1, the reference's weight
    tree has the program's structure and shapes."""
    c = conf()
    c["model"]["n_layers"] = 1
    cfg = harness().program_config(c)
    from repro.models.lm import init_lm_params
    want = jax.eval_shape(lambda: init_lm_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: mod(c["reference"]).init_weights(
        c["model"], jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(got)
    assert [a.shape for a in jax.tree.leaves(want)] == \
        [a.shape for a in jax.tree.leaves(got)]


def test_reference_draw_lets_a_query_meet_its_own_key():
    """At head 128, the program's query and key of one position (after
    the attention multipliers) meet about sqrt(128) = 11.3 above zero,
    those of two positions about 0 with a spread of sqrt(2 + 128 / D)
    (1.5 at D 512): so attention keeps a share on each query's own key,
    and a prefill that drops the query's own chunk or half its bucket
    moves the logits."""
    m = _tiny_model()
    m.update(d_model=512)
    m["attn"].update(head_dim=128)
    u = m["mup"]
    w = mod(conf()["reference"]).init_weights(
        m, jax.random.PRNGKey(1), dtype=jax.numpy.float32)
    a = w["segments"][0][0]["attn"]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (512, 512)))
    group = m["attn"]["n_heads"] // m["attn"]["n_kv_heads"]
    q = np.einsum("td,dhk->thk", h * u["attn_in"], np.asarray(a["wq"][0]))
    k = np.einsum("td,dhk->thk", h * u["attn_in"], np.asarray(a["wk"][0])) \
        * u["key"]
    k = np.repeat(k, group, axis=1)
    s = np.einsum("thk,shk->hts", q, k) / np.sqrt(128)
    own = np.einsum("htt->ht", s)
    other = s[:, ~np.eye(512, dtype=bool)]
    assert 9 < own.mean() < 14
    assert abs(other.mean()) < 0.1 and 1.35 < other.std() < 1.65


def test_benchmark_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cfgs = {c["name"]: c for c in b["configs"]}
    assert cfgs["falcon-h1-34b"]["reduced"] == ["num_hidden_layers"]
    cells = {w["name"]: w for w in b["workloads"]}
    for name, config in (("mamba2_longdoc_64k", "mamba2-2.7b"),
                         ("falconh1_longdoc_64k", "falcon-h1-34b")):
        assert (cells[name]["config"], cells[name]["traffic"],
                cells[name]["chips"]) == (config, "longdoc_64k", 1)
    per = {m["name"]: m for m in b["per_layer"]}
    for name in ("flash_prefill_roofline", "prefill_step_mfu_hybrid"):
        assert per[name]["workloads"] == ["falconh1_longdoc_64k"]
        assert per[name]["moves"] == "tokens_per_s"
        assert per[name]["source"] == "device_trace"
    # the device's idle share is read in both new cells: the accepted
    # list with the two appended, nothing else changed
    assert per["device_idle_share"]["workloads"] == [
        "mamba2_longdoc_32k", "mamba2_chat_bursty", "mamba2_longdoc_64k",
        "falconh1_longdoc_64k"]
    mix = harness().read_json("traffic", "longdoc_64k.json")
    sizes = mod("loadgen/common.py").quantile_sizes(mix["prompt"], 8)
    # every prompt with its answer fits, and is longer than half the top
    # KV rung (flash_prefill's least live prefix holds for every row)
    assert max(sizes) + mix["output"]["fixed"] < mix["engine"]["max_seq"]
    assert min(sizes) > mix["engine"]["max_seq"] // 2


FLASH = ("jit_chunk_step/%flash_prefill_kv65536.10 = bf16[1,20,256,128] "
         "custom-call")


def _ctx(tokens, ops, modules):
    return {"summary": {"window_s": 1.0, "busy_s": 0.9, "devices": 1,
                        "ops": {k: v[0] for k, v in ops.items()},
                        "ops_n": {k: v[1] for k, v in ops.items()},
                        "modules": {k: v[0] for k, v in modules.items()},
                        "modules_n": {k: v[1] for k, v in modules.items()},
                        "idle_by_span": {}},
            "model": conf()["model"],
            "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {"ckpt_ms": 0.0, "prefill_tokens": tokens,
                         "decode_tokens": 0},
            "engine": {"chunk": 256, "decode_block": 8}}


def test_metric_readers():
    ctx = _ctx(512, {FLASH: (0.05, 8)}, {"jit_chunk_step": (0.5, 2)})
    k = mod("kernels/flash_prefill.py")
    f, b = k.work(ctx["model"], FLASH)
    assert mod("metrics/flash_prefill_roofline.py").read(ctx) == \
        pytest.approx(100 * 8 * max(f / 197e12, b / 819e9) / 0.05)
    w = mod("work_hybrid.py")
    pairs = w.attention_pairs([(8, 1, 256, 65536)], 512, 4)
    f, b = w.prefill_work(ctx["model"], 512, 2, 256, pairs)
    assert mod("metrics/prefill_step_mfu_hybrid.py").read(ctx) == \
        pytest.approx(100 * max(f / 197e12, b / 819e9) / 0.5)
    # nothing to read: no prompt token, or no flash call (a KV-free model)
    assert mod("metrics/prefill_step_mfu_hybrid.py").read(
        _ctx(0, {FLASH: (0.05, 8)}, {"jit_chunk_step": (0.5, 2)})) is None
    assert mod("metrics/prefill_step_mfu_hybrid.py").read(
        _ctx(512, {}, {"jit_chunk_step": (0.5, 2)})) is None
    assert mod("metrics/flash_prefill_roofline.py").read(
        _ctx(512, {}, {})) is None


def _tiny_model():
    m = conf()["model"]
    m.update(n_layers=2, d_model=64, d_ff=96, vocab_size=500,
             vocab_pad_multiple=16)
    m["attn"].update(n_heads=10, n_kv_heads=2, head_dim=16)
    m["ssm"].update(d_state=16, headdim=16, chunk=16, d_ssm=64)
    return m


def test_a_tiny_cell_is_correct_and_its_control_is_not():
    """The falconh1_longdoc_64k cell through run_cell at a CPU size: the
    engine's served tokens against the float32 reference, and the float8
    control, which has to lie above the limit."""
    run = harness()
    bench = run.read_json("..", "..", "BENCHMARK.json")
    cell = next(c for c in bench["workloads"]
                if c["name"] == "falconh1_longdoc_64k")
    c = conf()
    c["model"] = _tiny_model()
    mix = run.read_json("traffic", "longdoc_64k.json")
    mix["engine"]["max_seq"] = 512
    mix["check"]["ref_block"] = 128
    mix["prompt"].update(lo=260, hi=300)
    mix["output"] = {"fixed": 12}
    # bfloat16 program against the float32 reference reads 0 to 2e-2 at
    # this size, the float8 control 0.4 (CPU runs)
    out = run.run_cell(bench, cell, c, mix, seed=2**33 + 5, seconds=1.0,
                       trace=False,
                       limits={"max_logit_gap": 0.1, "requests_failed": 0},
                       per_layer=[], t_start=time.perf_counter(),
                       control=True)
    checks = out["checks"]
    assert checks["requests_failed"]["value"] == 0
    assert checks["max_logit_gap"]["value"] <= 0.1
    assert checks["control_max_logit_gap"]["value"] > 0.1
    assert out["compiles_in_window"] == 0

