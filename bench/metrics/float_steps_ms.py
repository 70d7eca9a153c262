"""Host float steps the client waits for after the answer arrives: the
within-15% frontier (``DSEResult.points``) and ``pareto()``, mean per
query answered in the window, ms."""


def read(run):
    done = run.completed
    if not done:
        return None
    return sum(r.t_done - r.t_result for r in done) / len(done) * 1e3
