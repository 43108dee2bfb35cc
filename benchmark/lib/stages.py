"""Where set-up's seconds go: one line on the log for each stage."""
import time


class StageClock:
    def __init__(self, log):
        self.log = log
        self.t = time.perf_counter()

    def __call__(self, what):
        now = time.perf_counter()
        print("set-up: %-28s %6.1f s" % (what, now - self.t), file=self.log)
        self.t = now
