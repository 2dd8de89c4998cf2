"""A run with its timed path broken underneath comes out not correct.

Each case drives the whole harness on the CPU (the look for a chip is the
only part skipped) with the engine's decode program wrapped so that it
commits one fault a serving cell can have: a token altered where it is
produced; a step that returns its cache state unchanged (the token's kv
is never written); half of the batch left out (its rows' tokens never
computed). A one-chip cell has no exchange between chips to leave out."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

import _tiny  # noqa: E402


def broken_decode(fault: str, vocab: int):
    from repro.serve import engine as E
    make = E.make_paged_decode_fn

    def factory(ms, pc, psv):
        f = make(ms, pc, psv)

        def g(params, caches, tok, pos, bt, poison, key):
            nxt, ok, new = f(params, caches, tok, pos, bt, poison, key)
            if fault == "token":
                nxt = (nxt + 1) % vocab
            elif fault == "state":
                new = caches
            elif fault == "half":
                nxt = nxt.at[nxt.shape[0] // 2:].set(0)
            return nxt, ok, new

        return g

    return factory


@pytest.mark.parametrize("fault", ["token", "state", "half"])
def test_fault_makes_the_run_not_correct(tmp_path, monkeypatch, fault):
    from repro.serve import engine as E
    monkeypatch.setattr(E, "make_paged_decode_fn",
                        broken_decode(fault, _tiny.MODEL["vocab_size"]))
    # Check every finished request, so that the sample holds rows of
    # both halves of the batch whatever the timing put them in.
    monkeypatch.setitem(_tiny.TRAFFIC["chat"], "check_requests", 1000)
    out = _tiny.execute(str(tmp_path), "tiny.chat", seed=31)
    gap = out["checks"]["max_gap_sigma"]
    assert not out["correct"], gap
    assert gap["value"] > 3 * gap["limit"]
