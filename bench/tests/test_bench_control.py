"""The check's control: the reference put in the program's place and
computed through float8 (one step below the bfloat16 the configurations
state) reads a widest logit gap far above the program's on the same
served tokens, so a limit between the two fails the control. Here at a
small size on the CPU; ``bench/control.py`` takes the same readings on
the chip at each cell's own size."""

import bench_tiny  # noqa: F401
import numpy as np
import pytest

from bench.families import dense
from bench.reference import dense as ref

SMALL = dict(bench_tiny.TINY_DENSE, name="small", hidden_size=256,
             num_attention_heads=8, num_key_value_heads=2,
             intermediate_size=512, vocab_size=4096, num_hidden_layers=4)
LIMIT = 0.03      # readings at this size: program <= 0.004, control >= 0.10


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 3])
def test_control_fails_where_the_program_passes(seed):
    params = dense.make_params(SMALL, seed)
    engine = dense.build_engine(SMALL, params)
    rng = np.random.default_rng(seed)
    reqs = [engine.submit(rng.integers(1, 4096, n).tolist(), max_new_tokens=24)
            for n in (40, 90, 130)]
    engine.run()
    got = ref.logit_gaps(SMALL, params, [(r.prompt, r.generated) for r in reqs],
                         control=True)
    program = max(g.max() for g in got["gap"])
    control = max(g.max() for g in got["control_gap"])
    assert program <= LIMIT < control
    assert control >= 3 * max(program, 1e-3)


def test_control_in_the_programs_place_reads_not_correct(tmp_path):
    """Through a whole run of the harness: with the fp8 control put in the
    program's place, the configuration's own limit judges it not correct,
    while the program on the same served tokens is correct."""
    root = bench_tiny.make_root(tmp_path, config=dict(SMALL, check=dict(
        bench_tiny.TINY_DENSE["check"], logit_gap_max=LIMIT)))
    out = bench_tiny.run(root, seconds=2.0, control=True)
    gap = out["checks"]["logit_gap_max"]
    assert out["correct"] is True and gap["limit"] == LIMIT
    assert out["control"]["correct"] is False, out["control"]
    assert out["control"]["logit_gap_max"] > LIMIT
