"""The latent-attention MoE family (``families/mla_moe.py``) and its plain
reference (``reference/mla_moe.py``) at a tiny size on the CPU: the
benchmark's weights fit the program's tree, the engine's chunked prefill
and paged decode agree with the reference's full forward in logits while
the fp8 control does not, a whole harness run of a tiny cell, and the new
operation counts and readers."""

import json

import bench_tiny
import jax
import numpy as np
import pytest

from bench import catalog
from bench.families import mla_moe as fam
from bench.reference import mla_moe as ref

TINY = {
    "source": "https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    "family": "mla_moe", "model_type": "deepseek_v3",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 4, "ep": {"chips": 2, "this_chip": 1},
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 50000.0, "initializer_range": 0.02, "name": "tiny",
    "engine": {"n_slots": 4, "max_seq": 256, "n_pages": 33, "page_size": 16,
               "prefill_chunk": 32},
    "check": {"logit_gap_max": 0.05, "min_tokens": 40},
}
TINY_AGENT = {
    "kind": "backlog", "depth": 4, "block": 16, "ramp": "turnover",
    "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.7, "min": 8,
               "max": 100},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 30},
}
# bf16 activations against float32: relative L2 error of a logit row is
# about 1-2% at three layers (the median row); a wrong page, offset, mask
# or expert is O(1) on most rows, and the fp8 control's median row is
# above 9%
MEDIAN_TOL, REL_TOL = 0.02, 0.05
# a token whose routing the reference decides by less than this (score
# plus bias of its k-th against its best unchosen expert, in some layer)
# may take the other expert at bf16, where the program's router logits lie
# about 1% from the reference's; such a token's row, and through
# attention the rows after it, may then miss REL_TOL
TIE = 0.01


def _shapes(tree):
    return jax.tree.map(lambda s: tuple(s.shape), tree,
                        is_leaf=lambda x: hasattr(x, "shape"))


@pytest.mark.parametrize("conf", ["tiny", "moonlight-16b-a3b-ep4"])
def test_weights_fit_the_programs_tree(conf):
    from repro.models import get_model

    if conf == "tiny":
        c = TINY
    else:
        c = catalog.Catalog().config(conf)
    specs = get_model(fam.program_config(c)).param_specs
    assert _shapes(specs) == fam.shapes(c)
    if conf != "tiny":
        m = fam.dims(c)
        assert (m["E"], m["E_held"], m["k"], m["latent"]) == (64, 16, 6, 576)
        assert fam.param_count(c, embedding=True) == pytest.approx(5.16e9,
                                                                   rel=2e-3)


def _capture(engine, rows, reqs):
    """Record each logit row the engine's two jitted steps compute, with
    (request, position): a decode lane whose page-table row is not all
    scratch, and a prefill chunk that ends its prompt."""
    decode, chunk = engine._decode_paged, engine._prefill_chunk

    def owner(slot):
        return next(r for r in reqs if r.slot == slot)

    def decode_w(params, cache, batch):
        pos = np.asarray(batch["positions"])
        table = np.asarray(batch["page_table"])
        out = decode(params, cache, batch)
        for i in np.flatnonzero(table.any(axis=1)):
            rows.append((owner(i).req_id, int(pos[i]),
                         np.asarray(out[0][i], np.float32)))
        return out

    def chunk_w(params, cache, batch, **kw):
        r = owner(int(np.asarray(batch["slot"])))
        out = chunk(params, cache, batch, **kw)
        if kw["offset"] + int(np.asarray(batch["valid"])) == len(r.prompt):
            rows.append((r.req_id, len(r.prompt) - 1,
                         np.asarray(out[0][0], np.float32)))
        return out

    engine._decode_paged, engine._prefill_chunk = decode_w, chunk_w


@pytest.mark.parametrize("seed", [5, 2**33 + 11])
def test_engine_matches_reference_and_the_control_does_not(seed):
    """Chunked prefill (prompts of 1-3 chunks) then paged decode, every
    logit row the engine computes against the reference's full forward at
    that position: the median row within ``MEDIAN_TOL``, four rows in five
    within ``REL_TOL``, and each row beyond it behind a token the
    reference routes within ``TIE`` of a tie. The fp8 control misses
    ``REL_TOL`` on every row."""
    params = fam.make_params(TINY, seed)
    engine = fam.build_engine(TINY, params)
    rows, live = [], []
    _capture(engine, rows, live)
    rng = np.random.default_rng(seed)
    live += [engine.submit(rng.integers(1, 512, n).tolist(), max_new_tokens=12)
             for n in (70, 20, 45)]
    engine.run()
    toks = {r.req_id: r.prompt + r.generated for r in live}
    full = {k: ref.full_logits(TINY, params, t) for k, t in toks.items()}
    ctl = {k: ref.full_logits(TINY, params, t, quant=True)
           for k, t in toks.items()}
    margin = {k: ref.router_margins(TINY, params, t) for k, t in toks.items()}
    errs = []
    for rid, pos, got in rows:
        want = full[rid][pos]
        size = np.linalg.norm(want)
        assert np.linalg.norm(ctl[rid][pos] - want) > REL_TOL * size
        errs.append(np.linalg.norm(got - want) / size)
        if errs[-1] >= REL_TOL:
            assert margin[rid][:pos + 1].min() < TIE, (rid, pos, errs[-1])
    assert len(rows) == 3 * 12
    assert np.median(errs) < MEDIAN_TOL
    assert np.mean(np.asarray(errs) < REL_TOL) >= 0.8


def test_the_checks_reading_is_each_requests_mean_gap():
    """The check reads, at every served token, its request's mean gap, for
    the program's tokens and for the control's."""
    params = fam.make_params(TINY, 3)
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(1, 512, n).tolist(), rng.integers(1, 512, g).tolist())
            for n, g in ((20, 9), (41, 5))]
    tok = ref.token_gaps(TINY, params, seqs, control=True)
    got = ref.logit_gaps(TINY, params, seqs, control=True)
    for key in ("gap", "control_gap"):
        assert [len(g) for g in tok[key]] == [len(g) for g in got[key]] == [9, 5]
        assert all(g.min() >= 0 for g in tok[key])
        for t, g in zip(tok[key], got[key]):
            np.testing.assert_allclose(g, t.mean(), rtol=1e-6)


def test_harness_runs_the_cell_and_reads_its_metrics(tmp_path):
    """A whole run of a tiny agent-batch cell through the harness, and its
    traced run: the check compares served tokens with the reference and
    the control, and the per-layer readers that need no device trace
    read."""
    names = ["cache.pages_live_share.agent", "step.mfu.agent",
             "kernel.moe_gmm_roofline.agent",
             "kernel.mla_decode_attention_roofline.agent"] + STATS_READERS
    root = bench_tiny.make_root(tmp_path, config=TINY, traffic=TINY_AGENT,
                                per_layer=[(n, "%") for n in names])
    out = bench_tiny.run(root, seed=2**40 + 3, seconds=2.0, control=True)
    gap = out["checks"]["logit_gap_max"]
    assert gap["limit"] == 0.05 and gap["value"] >= 0.0
    assert out["control"]["logit_gap_max"] is not None
    assert out["failed"] == 0 and out["attempted"] > 4
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s",
                                   "ttft_p95_ms"}
    traced = bench_tiny.run(root, seed=7, seconds=2.0, trace=True)
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0 < got["cache.pages_live_share.agent"] <= 100
    assert 0 < got["scheduler.decode_lanes_mean.agent"] <= 4
    assert got["setup.compile_s.agent"] > 0
    for name in ("host.gap_ms_per_step.agent", "host.caller_ms_per_step.agent"):
        assert got[name] >= 0


# readers of the engine's counters and the set-up clock, copies of the
# other cells' under this cell's names
STATS_READERS = ["setup.compile_s.agent", "scheduler.decode_lanes_mean.agent",
                 "host.gap_ms_per_step.agent", "host.caller_ms_per_step.agent"]


def _ctx(reduced, calls, stats=None):
    return {"conf": dict(TINY), "family": fam, "calls": calls,
            "trace": reduced, "stats": stats or {},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "roofline": catalog.Catalog().roofline, "chips": 1,
            "setup_compile_s": None, "decode_lanes": []}


COUNTED = {"moe_decode_calls": 10, "moe_decode_hits": 25,
           "moe_decode_rows": 40, "moe_prefill_calls": 2,
           "moe_prefill_hits": 8, "moe_prefill_rows": 64}


@pytest.mark.parametrize("name", ["step.mfu.agent",
                                  "kernel.moe_gmm_roofline.agent",
                                  "kernel.mla_decode_attention_roofline.agent",
                                  "cache.pages_live_share.agent",
                                  "device.idle_share.agent"] + STATS_READERS)
def test_new_readers_give_none_not_zero_on_nothing(name):
    read = catalog.Catalog().reader(name)
    calls = [("decode", [10, 20], 2), ("prefill", 0, 32, True)]
    empty = {"ops": {}, "programs": {}, "window_s": 6.0, "busy_s": 0.0}
    if name != "device.idle_share.agent":
        assert read(_ctx(empty, [])) is None
    if name not in STATS_READERS:
        assert read(_ctx(None, calls, COUNTED)) is None
    if name.startswith("kernel."):
        assert read(_ctx(empty, calls, COUNTED)) is None    # no kernel time
    if name.startswith("cache.") or name.startswith("kernel.moe"):
        # an engine without the counters (the parent commit's)
        ran = {"ops": {"moe_gmm_swiglu": 1e-3}, "programs": {},
               "window_s": 6.0, "busy_s": 1.0}
        assert read(_ctx(ran, calls, {"decode_steps": 5})) is None


def test_moe_gmm_roofline_counts_what_the_program_counted():
    """Each traced call counts its kind's mean experts read and rows per
    call over the window: here 2.5 experts and 4 rows per decode step, 4
    experts and 32 rows per prefill chunk, over 1 ms of kernel time."""
    read = catalog.Catalog().reader("kernel.moe_gmm_roofline.agent")
    calls = [("decode", [10, 20], 2)] * 3 + [("prefill", 0, 32, True)]
    ran = {"ops": {"moe_gmm_swiglu": 6e-4, "moe_gmm": 4e-4, "fusion": 1.0},
           "programs": {}, "window_s": 6.0, "busy_s": 1.0}
    d, f = 64, 32
    ideal = (3 * (2.5 * 3 * d * f + 4 * (2 * d + 2 * f)) * 2
             + (4 * 3 * d * f + 32 * (2 * d + 2 * f)) * 2) / 819e9
    got = read(_ctx(ran, calls, COUNTED))
    assert got == pytest.approx(100 * ideal / 1e-3)
    # more experts read for the same calls and time: a higher share
    more = dict(COUNTED, moe_decode_hits=40)
    assert read(_ctx(ran, calls, more)) > got


def test_counts_of_the_new_kernels():
    m = fam.dims(TINY)
    gmm = catalog.Catalog().roofline("moe_gmm")
    # 3 experts read and 5 rows: 6 d f = 6 * 64 * 32 FLOPs a row; three
    # d x f weights an expert, rows in, through the SwiGLU and out
    f, b = gmm.work(3, 5, m)
    assert f == 5 * 6 * 64 * 32
    assert b == (3 * 3 * 64 * 32 + 5 * (2 * 64 + 2 * 32)) * 2
    assert gmm.per_call(COUNTED, "decode") == (2.5, 4.0)
    assert gmm.per_call({"moe_decode_calls": 0}, "decode") is None
    att = catalog.Catalog().roofline("mla_decode_attention")
    f, b = att.work(("decode", [3, 10], 2), m)
    # per layer and key: scores over 40 latent dims, values over 32, 4 heads
    assert f == 3 * 4 * 13 * (2 * 40 + 2 * 32)
    assert b == 3 * (13 * 40 * 2 + 2 * (2 * 4 * 40 * 2))


def test_model_flops_of_the_share():
    m = fam.dims(TINY)
    shapes = fam.shapes(TINY)
    experts = 3 * 2 * 4 * 64 * 32           # wg, wu, wd of 4 held, 2 layers
    outside = fam.param_count(TINY, embedding=False) - experts
    assert experts == sum(np.prod(shapes["moe_layers"]["mlp"][k])
                          for k in ("wg", "wu", "wd"))
    per_token = 2 * outside + 2 * 1.0 * 6 * 64 * 32
    att = 3 * 4 * (2 * 24 + 2 * 16)
    got = fam.call_flops(TINY, ("decode", [4, 6], 2))
    assert got == pytest.approx(2 * per_token + att * 10 + 2 * 2 * 64 * 512)
    got = fam.call_flops(TINY, ("prefill", 2, 3, False))
    assert got == pytest.approx(3 * per_token + att * (3 * 2 + 6))
    assert m["E"] == 8 and m["E_first"] == 4


def test_configuration_file_keeps_the_catalogs_numbers():
    """Every number of the published config is in the file under its key,
    but the experts held, which ``reduced`` lists beside the published
    count; the deployment and the check are stated."""
    c = json.loads((catalog.ROOT / "bench" / "configs"
                    / "moonlight-16b-a3b-ep4.json").read_text())
    assert c["reduced"] == ["n_routed_experts"]
    assert (c["n_routed_experts"], c["published"]["n_routed_experts"]) == (16, 64)
    assert c["ep"] == {"chips": 4, "this_chip": 0}
    assert (c["hidden_size"], c["num_hidden_layers"], c["kv_lora_rank"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["vocab_size"]) == (2048, 27, 512, 1408, 6, 163840)
    assert c["scoring_func"] == "sigmoid" and c["topk_method"] == "noaux_tc"
    assert c["q_lora_rank"] is None and c["n_group"] == c["topk_group"] == 1
    assert c["check"]["logit_gap_max"] > 0 and c["deployment"]
