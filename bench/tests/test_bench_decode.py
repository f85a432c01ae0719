"""CPU rehearsals of the decode cell at the model's smoke size: the last
line, the control and the planted faults."""

import pytest

import rehearsal

CELL = "mamba2-decode-b64"


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_rehearsal_last_line(trace):
    result, _, _ = rehearsal.run(CELL, trace=trace)
    rehearsal.check_last_line(CELL, result, trace)


def test_control_fails_where_the_program_passes():
    _, checks, control = rehearsal.run(CELL, control=True)
    assert all(c.ok for c in checks), checks
    assert not all(c.ok for c in control), control


def _planted(monkeypatch, fault):
    """Replace the timed step by the program's own step with ``fault``
    applied to what it returns; set-up's priming is left sound."""
    from repro.launch import steps

    make = steps.make_decode_step

    def planted(cfg):
        serve = make(cfg)

        def serve_step(params, cache, batch):
            return fault(cache, *serve(params, cache, batch))
        return serve_step
    monkeypatch.setattr(steps, "make_decode_step", planted)


@pytest.fixture
def stale_state(monkeypatch):
    """The timed step hands back the cache it was given."""
    _planted(monkeypatch, lambda old, logits, new: (logits, old))


@pytest.fixture
def half_batch(monkeypatch):
    """The timed step writes the new state for half of the batch only."""
    import jax
    import jax.numpy as jnp

    def half(old, logits, new):
        def keep(a, b):
            if a.ndim < 2:
                return b
            h = a.shape[1] // 2
            return jnp.concatenate([b[:, :h], a[:, h:]], axis=1)
        return logits, {"segments": jax.tree.map(keep, old["segments"],
                                                 new["segments"]),
                        "pos": new["pos"]}
    _planted(monkeypatch, half)


@pytest.fixture
def altered_token(monkeypatch):
    """The timed step's logits altered where they are produced."""
    _planted(monkeypatch, lambda old, logits, new: (
        logits.at[..., 0].add(1e3), new))


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_token"])
def test_planted_fault_reads_not_correct(fault, request):
    request.getfixturevalue(fault)
    result, _, _ = rehearsal.run(CELL)
    assert result["correct"] is False
