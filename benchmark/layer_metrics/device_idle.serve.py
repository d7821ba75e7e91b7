"""Share of the traced span in which no operation ran on the device."""

from benchmark.trace import idle_percent


def read(obs):
    return idle_percent(obs.get("trace"))
