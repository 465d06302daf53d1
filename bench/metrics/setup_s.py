"""Process start to the first timed step: compile or cache loads,
weights, optimizer state, planning, warm-up and the checked steps."""


def read(rec):
    return rec["setup_s"]
