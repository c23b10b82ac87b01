"""Mean ``SaveResult.ack_s`` of the saves in the window: how long
``save()`` held the loop before returning (device-to-host copy not
included; the loop makes it before calling ``save``)."""


def read(run):
    if not run.saves:
        return None
    return sum(r.ack_s for r in run.saves) / len(run.saves)
