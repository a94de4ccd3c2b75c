"""Server scheduler: milliseconds of stall a second of the window
(``/debug/scheduler`` ``stallWatch.stallMsTotal``, after less before, over
the time to the window's last answer): gaps of over 50 ms with requests in
flight and none completed. Nothing to read from a program without the
watch."""


def read(ctx):
    after = ctx["after"]["scheduler"].get("stallWatch")
    before = ctx["before"]["scheduler"].get("stallWatch")
    done = [r["done_s"] for r in ctx["records"] if "done_s" in r]
    if after is None or before is None or not done or max(done) <= 0:
        return None
    return (after["stallMsTotal"] - before["stallMsTotal"]) / max(done)
