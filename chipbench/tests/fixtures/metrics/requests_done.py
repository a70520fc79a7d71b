"""A per-layer metric that brings its own reader (no built-in reducer)."""


def reduce(obs):
    return obs["info"].get("completed_in_window")
