"""The control, at a size a test run holds: the tiny cell's checked
requests with the plain reference one precision step down in the
program's place must come out not correct, while the program's own
outputs are correct."""
import control
from bench_testkit import TINY_CONFIG, tiny_copy


def test_control_is_not_correct(tmp_path, capsys):
    root = tiny_copy(tmp_path)
    summary = control.readings(root, "tiny.mix", [2 ** 31 + 11], 1.0, 1,
                               platform=None)
    limits = TINY_CONFIG["limits"]
    assert summary["sound_correct"] == [True]
    assert summary["control_correct"] == [False]
    assert summary["programs"]
    for prog, r in summary["programs"].items():
        assert r["lower"] <= limits[prog], (prog, r)
        assert r["upper"] > limits[prog], (prog, r)
