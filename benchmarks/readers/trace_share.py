"""The matching operations' share of device busy time; percent."""


def read(obs, ctx, op=None, module=None, kernel=None):
    trace = obs.get("trace")
    if trace is None:
        return None
    seconds, n = trace.op_seconds(op, module, kernel=kernel)
    busy = trace.busy_by_device()[0]
    if not n or busy <= 0:
        return None
    return 100.0 * seconds / busy
