"""A number from the load generator's own record of the window:
``record`` is ``late_ms`` (send instant - due instant) or ``bind_ms``
(bind seen - due instant), ``field`` one of its statistics."""


def read(ctx, record, field, **_args):
    return (ctx["client"].get(record) or {}).get(field)
