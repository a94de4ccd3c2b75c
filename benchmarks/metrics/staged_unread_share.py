"""Engine residency: of the bytes the server holds staged after the window,
the share in residents that no query read during it (``/debug/memory``
``stagedSegments``: a resident whose ``touch`` stamp is the same after the
window as before it). HBM held for nothing. Nothing to read from a program
whose snapshot carries no ``touch``."""


def read(ctx):
    before = ctx["before"]["memory"].get("stagedSegments") or {}
    after = ctx["after"]["memory"]
    residents = after.get("stagedSegments") or {}
    total = after.get("stagedBytes") or 0
    if total <= 0 or not residents or any(
            "touch" not in r for r in residents.values()):
        return None
    unread = sum(r["bytes"] for name, r in residents.items()
                 if name in before
                 and before[name].get("touch") == r["touch"])
    return 100.0 * unread / total
