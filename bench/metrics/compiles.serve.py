"""Compiles in the measured window of a serving cell (the copied
CompileClock): any is a shape the warm-up missed."""


def read(obs):
    return obs["compiles"] if "capacity" in obs else None
