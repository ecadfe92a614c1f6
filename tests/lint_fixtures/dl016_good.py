"""DL016 good fixture: every program-construction scope declared in
PROGRAM_SITES — instrumented scopes carry their ledger hook with the
declared label, exempt scopes carry None."""

import jax

from das_tpu.obs import proflog

PROGRAM_SITES = {
    "dl016_good.build_program": "prog",
    "dl016_good._tiny_op": None,
}


def build_program(sig):
    def fn(x):
        return x + 1

    return proflog.instrument(
        "prog", proflog.sig_digest(sig), jax.jit(fn)
    )


@jax.jit
def _tiny_op(x):
    return x * 2
