"""The control: the plain reference in the program's place at the nearest
precision below the configuration's (three bf16 passes) has to come out
as not correct, through the same window and comparison."""
from harness.control import control_runtime


def test_control_is_not_correct(run_tiny):
    for seed in (3, 4, 2 ** 31 + 5):
        r = run_tiny(seed=seed, serve=control_runtime)
        c = r["checks"]
        assert r["correct"] is False
        assert c["dist_gap"]["value"] > c["dist_gap"]["limit"]
        # the control's ids are (all but) exact: only its distances fail
        assert c["recall_gap"]["value"] <= c["recall_gap"]["limit"]
        assert c["unanswered"]["value"] == c["bad_rows"]["value"] == 0
