"""``correct`` comes out false when the timed path is broken underneath
(a run driven without the look for a chip, at the tiny size), and so
does the control, the reference computed in fp8 (a precision below the
configuration's bf16) in the program's place."""
import jax
import pytest

from conftest import benchmark, run_tiny


def _altered_token(engine):
    """Every decode step serves the next token id after its own."""
    step, vocab = engine._paged_decode, engine.cfg.vocab_size

    def wrapper(*args):
        tok, ok, caches = step(*args)
        return (tok + 1) % vocab, ok, caches

    engine._paged_decode = wrapper


def _state_unchanged(engine):
    """Every decode step returns the KV pools it was given."""
    from repro.serve import engine as engine_mod
    fresh = jax.jit(engine_mod.make_paged_decode_fn(engine.cfg))

    def wrapper(params, caches, *rest):
        tok, ok, _ = fresh(params, caches, *rest)
        return tok, ok, caches

    engine._paged_decode = wrapper


def _half_batch(engine):
    """The decode step leaves the second half of the slots out."""
    step = engine._paged_decode

    def wrapper(*args):
        tok, ok, caches = step(*args)
        return tok.at[tok.shape[0] // 2:].set(0), ok, caches

    engine._paged_decode = wrapper


@pytest.mark.parametrize("cell,fault", [
    ("qwen3-0.6b.chat", _altered_token),
    ("qwen3-0.6b.chat", _state_unchanged),
    ("olmo-1b.batch", _half_batch)],          # a backlog fills every slot
    ids=["token_altered", "state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(tiny_root, cell, fault):
    res = run_tiny(tiny_root, cell, seed=31, hook=fault)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell",
                         [w["name"] for w in benchmark()["workloads"]])
def test_fp8_reference_control_is_not_correct(tiny_root, cell):
    """The control: the reference in fp8 in the program's place, judged
    on the same prompts and served tokens by the same comparison, reads
    ``correct`` false where the program's own run reads true."""
    d = {}
    res = run_tiny(tiny_root, cell, seed=1, control=True, details=d)
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False
    assert gap["value"] == d["verdict"]["control_gap"]
    assert gap["value"] > 3 * gap["limit"]
    assert d["verdict"]["max_logit_gap"] < gap["limit"]
