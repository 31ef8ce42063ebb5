"""tick_ms.<serving mix>: the router's tick time for the replica: /healthz ``tick_ema_s`` read when
the window closes (an exponential average the program keeps, alpha 0.3; not
a median), in milliseconds."""


def read(res):
    v = res["facts"].get("tick_ema_ms")
    return None if not v else float(v)
