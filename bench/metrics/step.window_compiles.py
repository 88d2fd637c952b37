"""Programs obtained inside the window: each XLA compile or persistent-cache
load that JAX reports (one backend-compile event each), i.e. a program
traced again on the serving path."""


def read(ctx):
    w0, w1 = ctx["out"].window
    return sum(1 for e in ctx["compiles"].between(w0, w1)
               if e[0] == "compile")
