"""The verdict read walks discovery paths over the pulled search log.

``report()`` / ``discoveries()`` on a device engine pull the log once
and walk each discovery's parent chain over its arrays; the fp ->
parent dict is built only for the callers that need the whole record
(``generated_fingerprints()``, checkpoints, visitors). Each case pins,
on one checker:

* the chains walked over the arrays equal the chains walked through the
  dict once it has been built;
* ``unique_state_count()`` after ``report()`` (the engine's
  ``base + log`` count, no dict behind it) equals the size of the
  record the dict then holds;
* ``profile()['mirror_builds']`` is 0 after ``report()`` and 1 after
  ``generated_fingerprints()``; ``log_walks`` counts each walked path
  once (the second ``discoveries()`` reuses it).
"""

import io

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402


def _mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices("cpu")[:n]), ("shards",))


def _twopc():
    return (TwoPhaseSys(4).checker()
            .tpu_options(capacity=1 << 12, race=False).spawn_tpu().join())


def _paxos():
    from stateright_tpu.examples.paxos_packed import PackedPaxos
    return (PackedPaxos(1).checker()
            .tpu_options(capacity=1 << 12, fmax=64, race=False)
            .spawn_tpu().join())


def _twopc_symmetry():
    model = TwoPhaseSys(4)
    return (model.checker().symmetry_fn(model.representative)
            .tpu_options(capacity=1 << 12, fmax=64, race=False)
            .spawn_tpu().join())


def _sound():
    from stateright_tpu.core import Property
    from stateright_tpu.examples.increment import Increment

    class SoundIncrement(Increment):
        def properties(self):
            return super().properties() + [
                # falsifiable at a terminal state: a scalar witness
                Property.eventually("full count",
                                    lambda _, s: s[0] == self.n)]

        def packed_properties(self, words):
            import jax.numpy as jnp
            base = super().packed_properties(words)
            return jnp.concatenate(
                [base, jnp.stack([words[0] == self.n])])

        def cache_key(self):
            return ("verdict_walk_sound_increment", self.n)

    return (SoundIncrement(2).checker().sound_eventually()
            .tpu_options(capacity=1 << 12, fmax=32, race=False)
            .spawn_tpu().join())


def _resumed(tmp_path):
    path = tmp_path / "ckpt.npz"
    partial = (TwoPhaseSys(5).checker()
               .tpu_options(capacity=1 << 14, resumable=True, fmax=64,
                            chunk_steps=2, race=False)
               .target_state_count(300)
               .spawn_tpu().join())
    partial.save(path)
    return (TwoPhaseSys(5).checker()
            .tpu_options(capacity=1 << 14, race=False)
            .resume_from(path).spawn_tpu().join())


def _sharded():
    return (TwoPhaseSys(4).checker()
            .tpu_options(capacity=1 << 12, fmax=32, mesh=_mesh(2))
            .spawn_tpu().join())


CASES = {"2pc": _twopc, "paxos": _paxos, "2pc-symmetry": _twopc_symmetry,
         "sound": _sound, "resumed": _resumed, "mesh2": _sharded}


def _chains(ck):
    model = ck.model()
    return {name: [model.fingerprint(s) for s in path.into_states()]
            for name, path in ck.discoveries().items()}


@pytest.mark.parametrize("case", sorted(CASES))
def test_walk_over_the_log_equals_the_dict_walk(case, tmp_path):
    make = CASES[case]
    ck = make(tmp_path) if case == "resumed" else make()
    ck.report(io.StringIO())
    prof = ck.profile()
    assert prof["mirror_builds"] == 0
    walked = _chains(ck)
    assert walked, "the case must have a discovery to walk"
    scalar = {fp for fp in ck._discovery_fps.values()
              if not isinstance(fp, (list, tuple))}
    # one walk a witness, however often discoveries() is read
    assert ck.profile()["log_walks"] == len(scalar) >= 1
    unique = ck.unique_state_count()

    fps = ck.generated_fingerprints()
    assert ck.profile()["mirror_builds"] == 1
    assert ck.unique_state_count() == unique == len(ck._generated)
    assert unique == len(fps)
    assert _chains(ck) == walked  # now walked through the dict
    assert ck.profile()["log_walks"] == len(scalar)
    # the dict is built once, from the one pull
    ck.generated_fingerprints()
    assert ck.profile()["mirror_builds"] == 1
    assert ck.profile()["span_s.mirror.dict"] > 0.0
