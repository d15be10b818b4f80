"""The matching operations' share of device busy time; percent."""


def read(obs, ctx, op, module=None):
    trace = obs.get("trace")
    if trace is None:
        return None
    seconds, n = trace.op_seconds(op, module)
    busy = trace.busy_by_device()[0]
    if not n or busy <= 0:
        return None
    return 100.0 * seconds / busy
