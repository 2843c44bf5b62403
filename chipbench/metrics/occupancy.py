"""Mean share of the engine's slots that held a running request, over
the decode steps of the untraced window, in percent."""


def read(run):
    steps = run.obs.get('steps')
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / (len(steps) * run.obs['slots'])
