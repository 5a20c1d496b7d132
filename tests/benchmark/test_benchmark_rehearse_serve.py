"""The ``engine`` driver run end to end at a tiny size on the CPU
(``--rehearse``), its lower-precision control, and a run with its timed path
broken.  Nothing here is a device number: these runs check paths, arguments,
the result line and that ``correct`` can come out false.
"""

import pytest

from rehearsal import check_contracts_line, read_result, run_cell


@pytest.mark.parametrize("workload,trace", [
    ("tiny-t5.closed", 0),
    ("tiny-t5.paced", 1),
    ("tiny-t5.prompt-heavy", 0),   # an engine block: longer prompts, prefix cache
])
def test_rehearsal_ends_in_the_contracts_line(capsys, workload, trace):
    check_contracts_line(capsys, workload, trace)


def test_an_engine_block_overrides_the_configurations_geometry(capsys):
    code, out = run_cell(
        capsys, "tiny-t5.prompt-heavy", "--rehearse", "--trace", "0")
    assert code == 0 and read_result(out)["correct"] is True
    # prompts of 17 to 32 tokens: the configuration's max_input_len of 16
    # would have refused every one of them
    assert "engine options from the traffic file: " \
        "{'max_input_len': 32, 'prefix_cache_entries': 8}" in out


@pytest.mark.parametrize("block,error", [
    ({"max_imput_len": 32}, KeyError),          # neither callable takes it
    ({"telemetry": 1}, KeyError),               # the driver's own
    ({"page_size": "8"}, TypeError),            # not a number
])
def test_an_engine_block_is_checked_against_the_programs_parameters(
        capsys, monkeypatch, block, error):
    from benchmark import traffic

    load = traffic.load
    monkeypatch.setattr(
        traffic, "load", lambda path: dict(load(path), engine=block))
    with pytest.raises(error, match="engine option"):
        run_cell(capsys, "tiny-t5.closed", "--rehearse", "--trace", "0")


def test_serve_control_in_lower_precision_is_not_correct(capsys):
    code, out = run_cell(
        capsys, "tiny-t5.closed", "--rehearse", "--control", "--trace", "0",
        seed=11)
    assert code == 0 and read_result(out)["correct"] is True
    assert "control[fp8] correct: False" in out, out


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from tpu_pipelines.serving.generative import GenerativeEngine

    build_step = GenerativeEngine._build_step

    def altered(self, b, kv, fns):
        step = build_step(self, b, kv, fns)

        def run(params, state):
            state, nxt = step(params, state)
            return state, (nxt + 1) % 4096

        return run

    monkeypatch.setattr(GenerativeEngine, "_build_step", altered)
    code, out = run_cell(
        capsys, "tiny-t5.closed", "--rehearse", "--trace", "0")
    assert code == 0
    assert read_result(out)["correct"] is False


def test_a_compile_inside_the_window_fails_the_run(capsys, monkeypatch):
    """A step program that warm-up missed compiles under traffic: the
    engine counts it, and the run counts it as a failure.  Six seconds of
    window, as the routed-expert fixture has: the eight callers' first
    requests are all due before the window opens and none returns before
    the missed program has compiled, which under six busy test workers
    outlasts a window of 1.5 s, so that no request came due inside it and
    the run raised where it should have counted."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    warm = GenerativeEngine.warm

    def warm_and_forget(self):
        warm(self)
        self._step_fns.clear()

    monkeypatch.setattr(GenerativeEngine, "warm", warm_and_forget)
    code, out = run_cell(
        capsys, "tiny-t5.closed", "--rehearse", "--trace", "0", seconds=6)
    assert code == 0
    result = read_result(out)
    assert result["failed"] > 0 and result["correct"] is False
