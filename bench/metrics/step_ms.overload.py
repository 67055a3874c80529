"""Mean device time of the generate program (prefill and cached
decode) per stage call, from the trace."""

from bench.record import step_ms


def read(run):
    return step_ms(run)
