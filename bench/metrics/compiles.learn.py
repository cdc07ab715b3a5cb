"""Compiles (jaxpr lowerings) from the learner's first update to the end
of the window's run, from the service's ``RunResult.metrics["compiles"]``
(the registry counter ``jit_compiles_total``).  A warmed window reads 0;
a program that does not count compiles gives no reading."""


def read(ctx):
    n = ctx.result["service_metrics"].get("compiles")
    return None if n is None else float(n)
