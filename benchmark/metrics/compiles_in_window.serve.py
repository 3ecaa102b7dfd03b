"""Compile: programs really compiled between the window's opening and the
end of the drain (jax.monitoring backend-compile events less persistent-cache
hits). Expected 0: warm-up covers every shape the mix uses."""


def read(run):
    b, a = run["counters"]["before"], run["counters"]["after"]
    return float(a["compiles"] - b["compiles"])
