"""Host seconds of set-up inside ``Engine.load_platform`` (the program's
``platform.load`` span): the XML parse and the zones, hosts, links and
routes it builds."""

from lib.scopes import setup_span_s


def read(run):
    return setup_span_s(run, "platform.load")
