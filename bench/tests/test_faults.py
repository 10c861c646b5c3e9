"""With the timed path broken underneath, a run comes out not correct:
a decode step that returns its KV cache unchanged, and a served token
altered where it is produced, also where every request asks one token
and nothing decodes (the faults a served cell can have on one chip; it
has no batch mean and no exchange between chips)."""

import pytest

from bench import faults
from bench.tests import rehearse


@pytest.mark.parametrize("fault,one_token", [
    ("state_unchanged", False), ("token_altered", False),
    ("token_altered", True)])
def test_fault_is_not_correct(fault, one_token, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    result = rehearse.run(seed=2 ** 31 + 99, one_token=one_token)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
