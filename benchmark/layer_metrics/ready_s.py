"""Set-up without compiling and warming up: process start to a ready
worker or replica, and the traffic's lead-in."""


def read(facts):
    return facts["setup_s"] - facts["compile_s"]
