"""The reader of ``attn_live_share.decode``: the paged decode kernel's
live compute blocks over its launched blocks, from the program's
``attn_blocks_live`` / ``attn_blocks`` counters in the window's steps."""
import types

import pytest

from bench import run as RUN

READ = RUN.reader("attn_live_share.decode")


def ctx(records):
    window = types.SimpleNamespace(t_start=10.0, t_end=20.0,
                                   steps=[object()] * 2)
    program = types.SimpleNamespace(
        recorder=types.SimpleNamespace(records=records))
    return types.SimpleNamespace(window=window, program=program,
                                 trace_dir=None)


def rec(t0, **counts):
    return types.SimpleNamespace(t0=t0, t1=t0 + 0.3, spans=[],
                                 counts=dict(reads=5, **counts))


def test_share_of_the_windows_blocks_that_ran():
    records = [rec(9.0, attn_blocks_live=64, attn_blocks=64),  # before it
               rec(11.0, attn_blocks_live=20, attn_blocks=64),
               rec(12.0, attn_blocks_live=23, attn_blocks=64)]
    assert READ(ctx(records)) == pytest.approx(100.0 * 43 / 128)


def test_nothing_without_the_counters():
    """A program that does not count its decode blocks (the jnp gather,
    speculative steps, or a program from before the counters)."""
    assert READ(ctx([rec(11.0), rec(12.0)])) is None
    assert READ(types.SimpleNamespace(
        window=types.SimpleNamespace(t_start=10.0, t_end=20.0, steps=[]),
        program=None, trace_dir=None)) is None
