"""Faults planted in the served path, which a run has to read as not
correct: each returns (owner, attribute, replacement) for ``setattr``."""


def altered_token():
    """The third token of every request is replaced where it is produced,
    and fed back as the next input."""
    from repro.serving.engine import ServingEngine

    tick = ServingEngine._decode_tick

    def altered(self):
        tick(self)
        for req in self.slots:
            if req is not None and len(req.generated) == 3:
                req.generated[-1] = (req.generated[-1] + 1) % self.cfg.vocab
                req._next_input = req.generated[-1]

    return ServingEngine, "_decode_tick", altered


def stale_cache():
    """A decode step that returns its KV cache unchanged."""
    from repro.models import decoding as DEC

    step = DEC.decode_step

    def stale(params, cfg, cache, tokens, window=0):
        logits, _ = step(params, cfg, cache, tokens, window)
        return logits, cache

    return DEC, "decode_step", stale


FAULTS = {"altered_token": altered_token, "stale_cache": stale_cache}
