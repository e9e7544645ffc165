"""Host seconds in ``GraphSessionManager.open_session``: ordering, BVSS
build and engine construction (``core/policy.prepare``)."""


def read(run):
    return run.setup["prepare_s"]
