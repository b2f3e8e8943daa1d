"""Share of the requests due in the window that the server refused (503)."""


def read(obs):
    n = len(obs["records"])
    return 100.0 * obs["failed_reasons"].get("shed", 0) / n if n else None
