"""Compiles in the measured window of a planner cell (the copied
CompileClock): any is a program built inside the timed loop."""


def read(obs):
    return obs["compiles"] if "exec_stats" in obs else None
