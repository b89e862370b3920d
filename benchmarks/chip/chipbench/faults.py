"""Faults planted in the program under test, to show that ``correct``
catches them.  Each takes a ``patch(obj, name, value)`` function —
``setattr`` in a calibration process, ``monkeypatch.setattr`` in a test —
and must be planted before the system is built."""


def altered_token(patch) -> None:
    """The head's answer altered where it is produced: token 3 wins."""
    from repro.models import layers
    real = layers.logits

    def logits(p, cfg, x):
        return real(p, cfg, x).at[..., 3].add(100.0)
    patch(layers, "logits", logits)


def state_unchanged(patch) -> None:
    """A decode step that returns its cache unchanged."""
    from repro.models import dense
    real = dense.decode_step

    def decode_step(params, cfg, cache, token, pos):
        lg, _ = real(params, cfg, cache, token, pos)
        return lg, cache
    patch(dense, "decode_step", decode_step)


FAULTS = {"altered_token": altered_token, "state_unchanged": state_unchanged}
