"""The hyena-355m 8K training cell end to end on the CPU, at the tiny sizes
its configuration and traffic files name: a contract-shaped, correct last
line that never names a TPU."""
from test_bench_rehearsal import SEED, _check_line, _run

CELL = "train.hyena-355m.b2-l8192.fp32"


def test_cpu_rehearsal_hyena_355m_train_cell():
    p = _run(["--workload", CELL, "--seed", SEED, "--seconds", "1",
              "--trace", "0", "--cpu-rehearsal"])
    _check_line(p, "train_tokens_per_s")
