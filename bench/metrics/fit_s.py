"""fit_s: seconds per complete fit, the window's length over the fits it
completed (every fit ends with its factors on the device and its error
history on the host)."""


def read(rec):
    fits = rec.window.get("fits")
    return rec.window["window_s"] / fits if fits else None
