"""What lets a configuration of another family come as data files
alone: a family's own count of a decode step under the roofline, a
check prompt as long as the configuration says, every counter of the
program by the window, and a rehearsal tree of any family."""

import json
import textwrap

import jax
import pytest

import rehearsal_util
from perfbench import harness, metrics, peaks, run, xplane
from perfbench.families import mistral
from test_perfbench_xplane import synthetic

BENCH = rehearsal_util.real_benchmark()
# A family of the test's own: family `mistral`'s decoder and reference,
# and a count that is not linear in depth, as a window layer's is.
WINDOWED = textwrap.dedent("""
    from perfbench.families.mistral import (  # noqa: F401
        build_decoder, make_params, reference_logits,
    )

    WINDOW = 16


    def decode_step_counts(model, weight_bytes, depths):
        rows = sum(min(d, WINDOW) for d in depths)
        return weight_bytes + 1000.0 * rows, 7.0 * len(depths)
""")
CHECK_PROMPT_TOKENS = 40


def read_roofline(run_):
    path = harness.find(rehearsal_util.REPO, BENCH, "layer_metrics", "decode_step_roofline.py")
    return harness.load_module(path).read(run_)


def traced_run(family, model):
    """The two ticks of the synthetic trace (device busy 60 and 70 ms),
    with three and two live slots."""
    run_ = harness.Run(
        workload={}, model=model, family=family, server_args={}, traffic={}, cell={},
        chips=1, peaks=peaks.PEAKS["TPU v5 lite"], weight_bytes=10**9,
        pool_bytes=0, seconds=1.0, t_start=0.0, t_open=0.0, t_close=1.0,
    )
    run_.tick_depths = [(8, 20, 300), (12, 500)]
    run_.ticks = [
        (0.1 * k, 0.1 * k + 0.1, len(d), len(d), sum(d))
        for k, d in enumerate(run_.tick_depths)
    ]
    run_.trace = xplane.reduce_profile(synthetic())
    return run_


@pytest.fixture
def windowed(tmp_path):
    (tmp_path / "windowed.py").write_text(WINDOWED)
    return harness.load_module(str(tmp_path / "windowed.py"))


def test_the_roofline_reads_the_familys_own_count(windowed):
    run_ = traced_run(windowed, dict(rehearsal_util.TINY_MODEL))
    # Tick 1: 8 + 16 + 16 rows, tick 2: 12 + 16; bytes bound both.
    least = [(1e9 + 1000.0 * rows) / 819e9 for rows in (40, 28)]
    assert read_roofline(run_) == pytest.approx(100 * sum(least) / 2 / 0.065, rel=1e-12)
    # Ticks outside the window are left out, depths and all.
    run_.t_close = 0.15
    assert read_roofline(run_) == pytest.approx(100 * least[0] / 0.065, rel=1e-12)
    run_.trace = None
    assert read_roofline(run_) is None


def test_family_mistral_brings_no_count_and_reads_the_number_it_read():
    assert not hasattr(mistral, "decode_step_counts")
    model = harness.load_json(harness.find(
        rehearsal_util.REPO, BENCH, "configs", BENCH["configs"][0]["name"] + ".json"
    ))
    run_ = traced_run(mistral, model)
    ticks = run_.window_ticks()
    # The reader as it was: the least time at the mean live slots and rows.
    was, _ = peaks.decode_step_least_s(
        run_.weight_bytes, sum(t[3] for t in ticks) / len(ticks),
        sum(t[4] for t in ticks) / len(ticks), model, run_.peaks, run_.chips,
    )
    busy = metrics.median(xplane.busy_per_span(run_.trace, "tick"))
    assert read_roofline(run_) == pytest.approx(100.0 * was / busy, rel=1e-12)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """One toy run of a cell whose configuration is of the family
    `windowed` and states `check_prompt_tokens`: its result, its
    details and the `Run` its readers were handed."""
    families = tmp_path_factory.mktemp("families")
    (families / "windowed.py").write_text(WINDOWED)
    model = dict(
        rehearsal_util.TINY_MODEL, family="windowed",
        check_prompt_tokens=CHECK_PROMPT_TOKENS,
    )
    root = rehearsal_util.tiny_root(
        str(tmp_path_factory.mktemp("root")), model=model, families=str(families)
    )
    seen, lines = [], []
    read_metrics = harness.read_metrics

    def spy(root_, bench, group, folder, run_):
        seen.append(run_)
        return read_metrics(root_, bench, group, folder, run_)

    with pytest.MonkeyPatch.context() as patch:
        patch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
        patch.setattr(harness, "read_metrics", spy)
        rc = run.main(
            ["--workload", "tiny.toy", "--seed", str(2**31 + 27), "--seconds", "2",
             "--trace", "0"],
            root=root, devices=jax.devices(), out=lines.append,
        )
    assert rc == 0 and len(seen) == 1
    return json.loads(lines[-1]), json.loads(lines[-2][len("details: "):]), seen[0]


def test_a_rehearsal_tree_takes_a_family_of_its_own(toy):
    result, _, run_ = toy
    assert result["correct"] is True and result["failed"] == 0
    assert run_.family.WINDOW == 16 and run_.model["family"] == "windowed"
    assert run_.family.decode_step_counts(run_.model, 5, (3, 40)) == (5 + 19000.0, 14.0)


def test_the_check_serves_as_many_tokens_as_the_configuration_states(toy):
    result, details, run_ = toy
    assert details["correct"]["prompt_tokens"] == CHECK_PROMPT_TOKENS
    # The check's request is the first the server was given.
    assert run_.rec.prompt_len[min(run_.rec.prompt_len)] == CHECK_PROMPT_TOKENS
    # Each number compared stands beside its limit, last in the line.
    assert list(result)[-1] == "compared"
    held = result["compared"]["behind_best_max"]
    assert held == {"value": details["correct"]["behind_best_max"], "limit": harness.MODEL_TOL}
    assert 0 <= held["value"] <= held["limit"]


def test_every_tick_has_its_slots_depths_beside_it(toy):
    _, _, run_ = toy
    assert len(run_.tick_depths) == len(run_.ticks) > 5
    for tick, depths in zip(run_.ticks, run_.tick_depths):
        assert (len(depths), sum(depths)) == (tick[3], tick[4])
        assert all(d >= 8 for d in depths)  # no prompt of the mix is shorter
    assert len(run_.window_tick_depths()) == len(run_.window_ticks())


def test_the_registry_by_the_window_holds_the_tokens_the_window_emitted(toy):
    _, _, run_ = toy
    # The series of this run's server: a worker that has run a flat
    # `DecodeServer` before this file holds that server's series too.
    (name,) = [
        k for k in run_.registry_close
        if k.startswith("defer_tokens_generated_total") and 'server="paged"' in k
    ]
    moved = run_.registry_close[name] - run_.registry_open[name]
    # Ticks and admissions are counted from the window's opening.
    assert moved == sum(t[2] for t in run_.ticks) + sum(a[2] for a in run_.admits) > 0
    assert moved == (
        run_.counters_close["tokens_generated"] - run_.counters_open["tokens_generated"]
    )
    # Set-up's tokens are in both snapshots, not in the difference.
    assert run_.registry_open[name] >= 8
    # Every kind of instrument is there by its exported name.
    assert any(isinstance(v, dict) and "buckets" in v for v in run_.registry_close.values())
    assert set(run_.registry_open) <= set(run_.registry_close)
