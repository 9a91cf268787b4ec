"""The comparison that decides ``correct``: its control and its faults fail.

At a test's size on the CPU, past the harness's look for a chip: a sound
run is correct; the reference in bfloat16 put in the program's place
fails a limit; and a run with the timed path broken underneath reads
``correct`` false, once for each fault a training cell can have.
"""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

jax = pytest.importorskip("jax")
from bench import control  # noqa: E402
from bench import rehearse  # noqa: E402
from bench.drivers import train_rounds  # noqa: E402

WORKLOAD = "pubmed-k100.train"


def test_sound_run_is_correct():
    res = rehearse.rehearse(WORKLOAD, 3_000_000_017, seconds=0.5)
    assert res.correct, [(c.name, c.value, c.limit) for c in res.checks]
    assert res.attempted > 0 and res.e2e["train_tokens_per_s"] > 0


def test_bfloat16_control_fails():
    rows = control.readings(WORKLOAD, 3_000_000_019, tiny=True,
                            control=True)
    prog, low = rows
    assert all(prog[k] <= v for k, v in train_rounds.LIMITS.items())
    assert any(low[k] > v for k, v in train_rounds.LIMITS.items())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "estep_bf16"])
def test_fault_reads_not_correct(fault):
    with control.fault(fault):
        res = rehearse.rehearse(WORKLOAD, 3_000_000_023, seconds=0.2)
    assert not res.correct, [(c.name, c.value) for c in res.checks]

