"""Mean admission wait of the window's requests, submit to launch, in ms
(``PCAResponse.waited``, the service's own counter)."""


def read(run):
    waited = run.counters.get("waited_s")
    if not waited:
        return None
    return 1e3 * sum(waited) / len(waited)
