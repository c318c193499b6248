"""The readings that set the limits of ``correct`` (``bench/calibrate.py``)
at a test size on the CPU: the control -- the plain reference computed in
bfloat16 in the program's place -- and the half-batch fault read above a
sound run of the program, and each is judged by the cell's limits."""
import pytest

from _bench_path import DATA, load, tiny

import calibrate
import run


@pytest.mark.parametrize("mix_file,expert_init,faults", [
    ("tiny_train.json", "copy", ("control", "half_batch")),
    ("tiny_chat.json", "copy_noise", ("control",)),
])
def test_control_and_faults_read_above_the_program(mix_file, expert_init,
                                                   faults):
    conf = tiny(expert_init=expert_init)
    line = calibrate.readings(conf, load(DATA / mix_file), 2 ** 31 + 41,
                              1.5, run.CompileClock())
    assert line["program_correct"] is True
    for name in faults:
        got = line[name]
        assert isinstance(got["correct"], bool)
        assert max(v for k, v in got.items() if k in line["program"]) > \
            max(line["program"].values())
    if "half_batch" in faults:
        assert line["half_batch"]["correct"] is False
