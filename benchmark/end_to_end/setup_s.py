"""Process start to the first measured step or request, compile included."""


def read(facts):
    return facts["setup_s"]
