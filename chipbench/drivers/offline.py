"""Serving an offline batch: every request of the mix is due when the window
opens, so the queue keeps every slot full; no move. The engine loop is
``open_loop``'s, whose schedule here is all at time zero."""
from chipbench.drivers.open_loop import ServeDriver

Driver = ServeDriver
