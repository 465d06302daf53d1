"""Device time of the Pallas calls over the device's busy time, in the
traced window."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return tr["pallas_s"] / (tr["busy_s"] * tr["chips"])
