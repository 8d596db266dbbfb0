"""Wall seconds a projector-refresh step takes beyond a steady step."""


def read(run):
    log = run.got["log"]
    if not log["refresh"] or not log["steady"]:
        return None
    return (sum(log["refresh"]) / len(log["refresh"])
            - sum(log["steady"]) / len(log["steady"]))
