"""Host clock around ``StandardUpdater.update`` returning (enqueue, no
fetch), median over the window's steps; recorded by the train driver."""


def read(view):
    return view["result"]["metrics"].get("train.dispatch_ms")
