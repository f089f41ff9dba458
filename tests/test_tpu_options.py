"""``CheckerBuilder.tpu_options`` accepts only the keys an engine reads.

A removed option (the fused Pallas path's ``fused``, ``fused_attempt``
and ``cc_dedup``) or a misspelt one would otherwise run the default
path and say nothing.
"""

import pytest

from stateright_tpu.models.twopc import TwoPhaseSys


@pytest.mark.parametrize("key", ["fused", "fused_attempt", "cc_dedup",
                                 "capacty"])
def test_unknown_key_raises_naming_it(key):
    builder = TwoPhaseSys(3).checker()
    with pytest.raises(ValueError, match=rf"\b{key}\b"):
        builder.tpu_options(**{key: True})
    # nothing of the refused call is kept
    assert key not in builder.tpu_options_
