"""Replay host path (``core/jaxplan.py``): the program's ``exec`` span less
its ``jit_replay`` span, every host stage of the replay (staging, transfers,
ledger replay, output split, owner-merge), per shuffle; the mean over the
traced window's calls."""
from chipbench.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "exec", minus="jit_replay")
