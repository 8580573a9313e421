"""Each test's changes to the shared CPU-test traffic mix stay its own.

``_smoke.cell`` hands out a shallow copy of ``_smoke.MIX``, so a test
that edits one of its length distributions in place edits the mix of
every later test in the same process."""
import copy

import _smoke
import pytest


@pytest.fixture(autouse=True)
def _fresh_mix():
    saved = copy.deepcopy(_smoke.MIX)
    yield
    _smoke.MIX.clear()
    _smoke.MIX.update(saved)
