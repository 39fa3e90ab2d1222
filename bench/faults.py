"""Faults planted under the timed path, each a piece of the program
replaced underneath a whole run: the tests check that each makes `correct`
come out false, and `limits.py --fault` reads each on the chip at a cell's
own size. `patches(name)` gives the (object, attribute, value) triples that
plant a fault; nothing here runs in a benchmark run."""
from __future__ import annotations


def _train_step(fault):
    import jax
    import jax.numpy as jnp

    from bench import program
    orig = program.train_step

    def train_step(mcfg, n_params, opt):
        opt_init, step = orig(mcfg, n_params, opt)
        real = step.__wrapped__

        def broken(params, opt_state, batch):
            if fault == "unchanged":
                return params, opt_state, real(params, opt_state, batch)[2]
            # half of the batch left out, the mean taken over the rest:
            # half of the rows, or of the positions where the batch is one
            # row
            b, n = batch["tokens"].shape
            mask = jnp.ones((b, n), jnp.float32)
            mask = mask.at[b // 2:].set(0.0) if b > 1 else \
                mask.at[:, n // 2:].set(0.0)
            return real(params, opt_state,
                        dict(batch, loss_mask=mask.at[:, -1].set(0.0)))

        return opt_init, jax.jit(broken, donate_argnums=(0, 1))

    return [(program, "train_step", train_step)]


def _altered_token():
    from repro.serve import engine as E
    orig = E.ServeEngine._emit

    def emit(self, slot, rid, tok, finished):
        return orig(self, slot, rid, (tok + 1) % self.cfg.vocab_size,
                    finished)

    return [(E.ServeEngine, "_emit", emit)]


def _decode_unchanged():
    from repro.serve import engine as E
    orig = E._tick

    def tick(params, state, *a, **k):
        out = orig(params, state, *a, **k)
        return (state,) + tuple(out[1:]) if k["do_decode"] else out

    return [(E, "_tick", tick)]


FAULTS = {
    "unchanged": lambda: _train_step("unchanged"),
    "half_batch": lambda: _train_step("half_batch"),
    "altered_token": _altered_token,
    "decode_unchanged": _decode_unchanged,
}


def patches(name):
    return FAULTS[name]()
