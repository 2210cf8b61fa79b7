"""The plain reference against the engine, at reduced phi4-mini and
qwen3 sizes on the CPU, on the benchmark's own weights: chunked prefill,
a prefix hit that starts mid-chunk, a whole-prompt hit with its
copy-on-write page (page-aligned, as the trie matches whole pages), and decode through the paged cache. Every logit row
the engine computes is compared with the reference's at its position."""

import bench_tiny  # noqa: F401
import numpy as np
import pytest

from bench.families import dense
from bench.reference import dense as ref

QWEN3_TINY = dict(bench_tiny.TINY_DENSE, model_type="qwen3", head_dim=24,
                  num_attention_heads=4, tie_word_embeddings=False,
                  rms_norm_eps=1e-6, rope_theta=1e6)
# bf16 activations against float32: relative L2 error of a logit row
# is a few 2^-8 at two layers; a wrong page, offset or mask is O(1)
REL_TOL = 0.05


def _capture(engine, rows, reqs):
    """Record each logit row the engine's two jitted steps compute, with
    (request, position), from the calls' own arguments: a decode lane
    whose page-table row is not all scratch, and a prefill chunk that
    ends its prompt. A slot's request is the one of ``reqs`` holding it."""
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


@pytest.mark.parametrize("conf", [bench_tiny.TINY_DENSE, QWEN3_TINY],
                         ids=["phi4-reduced", "qwen3-reduced"])
def test_engine_matches_reference(conf):
    conf = dict(conf, name="tiny")
    params = dense.make_params(conf, 2**33 + 5)
    engine = dense.build_engine(conf, params)
    rows, live = [], []
    _capture(engine, rows, live)
    rng = np.random.default_rng(0)
    a = rng.integers(1, 512, 70).tolist()              # 3 chunks of 32
    b = a[:48] + rng.integers(1, 512, 20).tolist()     # hit on 3 pages of 16
    ra = engine.submit(a, max_new_tokens=12)
    live.append(ra)
    engine.run()
    rb = engine.submit(b, max_new_tokens=12)
    # a page-aligned prompt whose pages are all cached: a whole-prompt
    # hit, its last token recomputed into a copy of its last page
    rc = engine.submit(a[:64], max_new_tokens=6)
    live += [rb, rc]
    engine.run()
    assert engine.stats["prefix_hits"] >= 2 and engine.stats["cow_copies"] >= 1
    assert engine.stats["prefill_tokens_shared"] >= 48 + 63
    reqs = {r.req_id: r for r in (ra, rb, rc)}
    full = {rid: ref.full_logits(conf, params, r.prompt + r.generated)
            for rid, r in reqs.items()}
    checked = 0
    for rid, pos, got in rows:
        want = full[rid][pos]
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < REL_TOL, (rid, pos, err)
        checked += 1
    assert checked >= 12 + 12 + 6
    for rid, r in reqs.items():
        lg = full[rid]
        for j, tok in enumerate(r.generated):
            row = lg[len(r.prompt) - 1 + j]
            assert row.max() - row[tok] < 0.05 * np.abs(row).max()


def test_logit_gaps_read_the_served_tokens():
    conf = dict(bench_tiny.TINY_DENSE, name="tiny")
    params = dense.make_params(conf, 1)
    prompt = list(range(1, 40))
    lg = ref.full_logits(conf, params, prompt)
    greedy = [int(lg[-1].argmax())]
    gaps = ref.logit_gaps(conf, params, [(prompt, greedy)])["gap"]
    assert gaps[0].shape == (1,) and gaps[0][0] == pytest.approx(0.0, abs=1e-5)
    worst = int(lg[-1].argmin())
    gaps = ref.logit_gaps(conf, params, [(prompt, [worst])])["gap"]
    assert gaps[0][0] == pytest.approx(lg[-1].max() - lg[-1].min(), rel=1e-4)
