"""Share of the prompt tokens admitted in the window that the prefix cache
served (lease tokens over prompt tokens of the window's admissions)."""


def read(run):
    adm = [p for s in run.steps for p in run.prefills.get(s, [])]
    prompt = sum(suffix + cached for suffix, cached in adm)
    return 100.0 * sum(cached for _, cached in adm) / prompt if prompt else None
