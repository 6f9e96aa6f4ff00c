"""The fdgui v2 frontend: one self-contained HTML page, no build step.

The reference bundles a compiled frontend into the gui tile binary
(fd_gui_tile.c serves it from memory); the python re-expression keeps
the same deployment shape with inline JS — the tile (and the headless
report) serve exactly this string, so the dashboard works with zero
assets, offline, from `file://`.

Two data paths, one renderer:

  * live: the page opens `ws://<host>/ws`, receives one `snapshot`
    then `delta` frames (gui/schema.py protocol), reconnects on drop;
    flamegraph and bench tabs fetch `/flame.json` / `/bench.json` on
    demand.
  * report: `window.FDGUI_DATA = {...}` is injected where the
    REPORT_MARKER comment sits (gui/report.py) and the page renders
    statically from it — same code, no server.

Rendered surfaces: live topology graph (links colored by activity /
backpressure, the saturating hop highlighted), per-tile occupancy
sparklines, the tile table (state / heartbeat / metrics / latency),
an SLO status + breach-history panel, an on-demand flamegraph view
over fdprof folded stacks, the bench-trend page over BENCH_r*.json
rounds, and a history tab backed by the fdflight on-disk archive
(`/history.json` live, `DATA.history` in reports) so sparklines
survive shm ring wraps and workspace teardown.
"""
from __future__ import annotations

REPORT_MARKER = "<!--FDGUI_DATA-->"

PAGE = r"""<!doctype html><html><head><meta charset="utf-8">
<title>fdgui &mdash; firedancer-tpu</title><style>
:root{--bg:#0b0e14;--panel:#11151f;--line:#1f2430;--fg:#d6d9e0;
--dim:#565f89;--acc:#7aa2f7;--ok:#9ece6a;--warn:#e0af68;--bad:#f7768e}
body{font-family:ui-monospace,monospace;background:var(--bg);
color:var(--fg);margin:18px}h1{font-size:16px;color:var(--acc);
margin:0 0 10px}small{color:var(--dim)}.badge{font-size:11px;
border:1px solid var(--line);border-radius:3px;padding:1px 6px;
color:var(--warn);margin-left:8px}.kpis{display:flex;gap:18px;
margin:8px 0}.kpi{background:var(--panel);border:1px solid var(--line);
border-radius:4px;padding:6px 14px}.kv{font-size:22px;color:var(--ok)}
.kv.bad{color:var(--bad)}.kl{font-size:11px;color:var(--dim)}
nav{margin:10px 0}nav button{background:var(--panel);color:var(--dim);
border:1px solid var(--line);padding:4px 12px;cursor:pointer;
font:inherit}nav button.on{color:var(--acc);border-color:var(--acc)}
table{border-collapse:collapse;margin-top:10px}td,th{padding:3px 10px;
border-bottom:1px solid var(--line);text-align:left;font-size:12px}
th{color:var(--acc)}.run{color:var(--ok)}.boot{color:var(--warn)}
.halt,.FAIL{color:var(--bad)}#graph{background:var(--panel);
border:1px solid var(--line);border-radius:4px}#sat{font-size:12px;
color:var(--bad);margin:6px 0;min-height:14px}
.frow{display:flex;height:17px;margin-top:1px}
.fcell{overflow:hidden;white-space:nowrap;font-size:10px;
color:#0b0e14;padding:1px 3px;border-radius:2px;margin-right:1px;
cursor:default}
.chart{background:var(--panel);border:1px solid var(--line);
border-radius:4px;margin:8px 0;padding:6px}
.chart h3{font-size:12px;color:var(--acc);margin:0 0 4px}
#flame h3{font-size:12px;color:var(--acc);margin:12px 0 2px}
#prov{background:var(--panel);border:1px solid var(--line);
border-radius:4px;padding:6px 12px;margin:8px 0;font-size:12px}
#prov .wbadge{display:inline-block;border:1px solid var(--line);
border-radius:3px;padding:0 6px;margin:1px 4px 1px 0;font-size:11px}
#prov .wit{color:var(--ok);border-color:var(--ok)}
#prov .fallb{color:var(--warn);border-color:var(--warn)}
#prov .wfail{color:var(--bad);border-color:var(--bad)}
</style></head><body>
<h1>firedancer-tpu <span id="topo"></span>
<small id="digest"></small><span id="mode" class="badge">live</span></h1>
<div class="kpis">
<div class="kpi"><div class="kv" id="tps">-</div><div class="kl">TPS</div></div>
<div class="kpi"><div class="kv" id="kbreach">0</div><div class="kl">SLO breached now</div></div>
<div class="kpi"><div class="kv" id="ktiles">-</div><div class="kl">tiles up</div></div>
<div class="kpi" id="kcatch" hidden><div class="kv" id="kbehind">-</div>
<div class="kl">slots behind <span id="kcdetail"></span></div></div>
</div>
<div id="prov" hidden></div>
<nav>
<button data-tab="topo" class="on">topology</button>
<button data-tab="slo">slo</button>
<button data-tab="flame">flamegraph</button>
<button data-tab="bench">bench trends</button>
<button data-tab="history">history</button>
</nav>
<section id="tab-topo">
<svg id="graph" width="960" height="10"></svg>
<div id="sat"></div>
<table id="tiles"><thead><tr><th>tile</th><th>kind</th><th>state</th>
<th>occupancy</th><th>hb age</th><th>work p99 &micro;s</th>
<th>metrics</th></tr></thead><tbody></tbody></table>
</section>
<section id="tab-slo" hidden>
<table id="slotab"><thead><tr><th>breached</th><th>total breaches</th>
</tr></thead><tbody><tr><td id="sbr">0</td><td id="sbs">0</td></tr>
</tbody></table>
<table id="sloev"><thead><tr><th>ts</th><th>target</th><th>value</th>
</tr></thead><tbody></tbody></table>
</section>
<section id="tab-flame" hidden><div id="flame">
<small>host-sampler folded stacks per profiled tile (fdprof)</small>
</div></section>
<section id="tab-bench" hidden><div id="bench">
<small>no bench rounds loaded</small></div></section>
<section id="tab-history" hidden><div id="history">
<small>no flight archive loaded (is [flight] enabled?)</small></div>
</section>
<!--FDGUI_DATA-->
<script>
"use strict";
const $=id=>document.getElementById(id);
const DATA=window.FDGUI_DATA||null;
let S=null,prev=null,occHist={},edgeEl={},nodeEl={},sparkEl={};
const fmt=v=>v>=1e6?(v/1e6).toFixed(1)+"M":v>=1e3?(v/1e3).toFixed(1)+"K"
  :(+v).toFixed(0);

/* ---- tabs ---- */
for(const b of document.querySelectorAll("nav button")){
 b.onclick=()=>{for(const x of document.querySelectorAll("nav button"))
   x.classList.toggle("on",x===b);
  for(const s of document.querySelectorAll("section"))
   s.hidden=s.id!=="tab-"+b.dataset.tab;
  if(b.dataset.tab==="flame")loadFlame();
  if(b.dataset.tab==="bench")loadBench();
  if(b.dataset.tab==="history")loadHistory();};}

/* ---- topology graph: longest-path layering, SVG nodes + edges ---- */
function layering(s){
 const depth={},prod={};
 for(const[ln,l]of Object.entries(s.links))if(l.producer)prod[ln]=l.producer;
 const d=(tn,seen)=>{if(depth[tn]!=null)return depth[tn];
  if(seen.has(tn))return 0;seen.add(tn);
  const ups=s.tiles[tn].ins.map(ln=>prod[ln]).filter(p=>p&&p!==tn);
  return depth[tn]=ups.length?1+Math.max(...ups.map(p=>d(p,seen))):0;};
 for(const tn in s.tiles)d(tn,new Set());
 const maxd=Math.max(0,...Object.values(depth));
 for(const tn in s.tiles){const t=s.tiles[tn];
  if(!t.ins.length&&!t.outs.length)depth[tn]=maxd+1;}
 return depth;
}
function buildGraph(){
 const svg=$("graph");svg.innerHTML="";edgeEl={};nodeEl={};
 const depth=layering(S),cols={};
 for(const tn in depth)(cols[depth[tn]]=cols[depth[tn]]||[]).push(tn);
 const ncol=Object.keys(cols).length,cw=Math.max(150,920/Math.max(1,ncol));
 const rows=Math.max(...Object.values(cols).map(c=>c.length));
 const H=Math.max(80,rows*46+20);svg.setAttribute("height",H);
 svg.setAttribute("width",Math.max(960,ncol*cw+40));
 const pos={};
 Object.keys(cols).sort((a,b)=>a-b).forEach((dstr,ci)=>{
  cols[dstr].sort().forEach((tn,ri)=>{
   pos[tn]=[20+ci*cw,14+ri*46];});});
 const NS="http://www.w3.org/2000/svg";
 for(const[ln,l]of Object.entries(S.links)){
  const p=l.producer;if(!p||!pos[p])continue;
  for(const c of l.consumers){if(!pos[c])continue;
   const e=document.createElementNS(NS,"path");
   const[x1,y1]=pos[p],[x2,y2]=pos[c];
   const sx=x1+120,sy=y1+14,ex=x2,ey=y2+14,mx=(sx+ex)/2;
   e.setAttribute("d",`M${sx},${sy} C${mx},${sy} ${mx},${ey} ${ex},${ey}`);
   e.setAttribute("fill","none");e.setAttribute("stroke","#565f89");
   e.setAttribute("stroke-width","1.5");
   const t=document.createElementNS(NS,"title");
   t.textContent=ln;e.appendChild(t);
   svg.appendChild(e);(edgeEl[ln]=edgeEl[ln]||[]).push(e);}}
 for(const tn in pos){const[x,y]=pos[tn];
  const g=document.createElementNS(NS,"g");
  const r=document.createElementNS(NS,"rect");
  r.setAttribute("x",x);r.setAttribute("y",y);
  r.setAttribute("width",120);r.setAttribute("height",28);
  r.setAttribute("rx",4);r.setAttribute("fill","#0b0e14");
  r.setAttribute("stroke","#565f89");
  const tx=document.createElementNS(NS,"text");
  tx.setAttribute("x",x+8);tx.setAttribute("y",y+18);
  tx.setAttribute("fill","#d6d9e0");tx.setAttribute("font-size","11");
  tx.textContent=tn+" ("+S.tiles[tn].kind+")";
  g.appendChild(r);g.appendChild(tx);svg.appendChild(g);
  nodeEl[tn]=r;}
}

/* ---- delta application ---- */
function linkRates(d){
 const out={};if(!d.links)return out;
 for(const[ln,rec]of Object.entries(d.links)){
  const p=prev&&prev.links&&prev.links[ln],dt=prev?(d.ts-prev.ts)/1e9:0;
  const cons=Object.values(rec.consumers||{});
  const lag=cons.length?Math.max(0,...cons.map(c=>c.lag||0)):0;
  out[ln]={pub:rec.pub,bp:rec.backpressure,lag,
   pubRate:p&&dt>0?Math.max(0,(rec.pub-p.pub)/dt):0,
   bpDelta:p?Math.max(0,rec.backpressure-p.backpressure):0};}
 return out;
}
function applyDelta(d){
 if(!S)return;
 $("tps").textContent=fmt(d.tps||0);
 const up=Object.values(d.tiles||{}).filter(t=>t.state==="run").length;
 $("ktiles").textContent=up+"/"+Object.keys(d.tiles||{}).length;
 const br=(d.slo&&d.slo.breach)||0;
 $("kbreach").textContent=br;
 $("kbreach").classList.toggle("bad",br>0);
 /* edges: gray idle, green flowing, amber lossy, red backpressured;
    the saturating hop = the link taking the most new bp ticks */
 const rates=linkRates(d);let sat=null,satBp=0;
 for(const[ln,r]of Object.entries(rates)){
  if(r.bpDelta>satBp){satBp=r.bpDelta;sat=ln;}}
 for(const[ln,els]of Object.entries(edgeEl)){
  const r=rates[ln];if(!r)continue;
  let col="#565f89",w=1.5;
  if(r.pubRate>0)col="#9ece6a";
  if(r.lag>0)col="#e0af68";
  if(r.bpDelta>0){col="#f7768e";w=2.5;}
  if(ln===sat&&satBp>0)w=4;
  for(const e of els){e.setAttribute("stroke",col);
   e.setAttribute("stroke-width",w);}}
 $("sat").textContent=sat&&satBp>0?
  "saturating hop: "+sat+" (+"+satBp+" backpressure ticks, "+
  "producer "+(S.links[sat]?S.links[sat].producer:"?")+")":"";
 /* tile nodes + table */
 const tb=document.querySelector("#tiles tbody");
 for(const[tn,row]of Object.entries(d.tiles||{})){
  if(nodeEl[tn])nodeEl[tn].setAttribute("stroke",
   row.state==="run"?"#9ece6a":row.state==="boot"?"#e0af68":"#f7768e");
  const occ=(row.occupancy&&row.occupancy.work)||0;
  (occHist[tn]=occHist[tn]||[]).push(occ);
  if(occHist[tn].length>60)occHist[tn].shift();
  let tr=document.getElementById("tr-"+tn);
  if(!tr){tr=document.createElement("tr");tr.id="tr-"+tn;
   tr.innerHTML="<td>"+tn+"</td><td>"+row.kind+"</td>"+
    "<td class='st'></td><td class='oc'></td><td class='hb'></td>"+
    "<td class='wk'></td><td class='ms'></td>";
   tb.appendChild(tr);}
  const st=tr.querySelector(".st");
  st.textContent=row.state;st.className="st "+row.state;
  tr.querySelector(".oc").innerHTML=spark(occHist[tn])+
   " "+(occ*100).toFixed(0)+"%"+
   (row.occupancy&&row.occupancy.tpu?
    " <small>tpu "+(row.occupancy.tpu*100).toFixed(0)+"%</small>":"");
  tr.querySelector(".hb").textContent=fmt(row.hb_age_ticks);
  const w=(row.latency&&row.latency.work)||{};
  tr.querySelector(".wk").textContent=w.count?w.p99_us.toFixed(0):"-";
  tr.querySelector(".ms").innerHTML="<small>"+
   Object.entries(row.metrics||{}).filter(([k,v])=>v)
   .map(([k,v])=>k+"="+fmt(v)).join(" ")+"</small>";}
 /* catch-up panel (follower topologies only: d.catchup != null) */
 const cu=d.catchup;
 $("kcatch").hidden=!cu;
 if(cu){
  $("kbehind").textContent=fmt(cu.behind||0);
  $("kbehind").classList.toggle("bad",!!cu.divergent_slot);
  let det="replay "+fmt(cu.replay_tps||0)+" tps";
  if(cu.restore_pct!=null&&cu.restore_pct<100)
   det="restore "+cu.restore_pct+"%";
  if(cu.divergent_slot)det="DIVERGED @ slot "+cu.divergent_slot;
  $("kcdetail").textContent="· "+det;}
 /* slo tab */
 if(d.slo){$("sbr").textContent=d.slo.breach||0;
  $("sbs").textContent=d.slo.breaches||0;
  const eb=document.querySelector("#sloev tbody");eb.innerHTML="";
  for(const e of(d.slo.events||[]).slice().reverse()){
   const tr=document.createElement("tr");
   tr.innerHTML="<td>"+e.ts+"</td><td class='FAIL'>"+e.target+
    "</td><td>"+(e.value==null?"-":fmt(e.value))+"</td>";
   eb.appendChild(tr);}}
 prev=d;
}
function spark(vals){
 const w=60,h=14,n=vals.length;if(!n)return"";
 const pts=vals.map((v,i)=>((i*(w-2)/Math.max(1,n-1))+1)+","+
  (h-1-Math.min(1,Math.max(0,v))*(h-2))).join(" ");
 return"<svg width='"+w+"' height='"+h+"'><polyline points='"+pts+
  "' fill='none' stroke='#7aa2f7' stroke-width='1'/></svg>";
}

/* ---- flamegraph over fdprof folded stacks ---- */
let flameLoaded=false;
function loadFlame(){
 if(flameLoaded)return;flameLoaded=true;
 if(DATA){renderFlame(DATA.flame||{});return;}
 fetch("flame.json").then(r=>r.json()).then(renderFlame)
  .catch(()=>{$("flame").innerHTML="<small>no profile data "+
   "(is [prof] enabled?)</small>";flameLoaded=false;});
}
const FLAMECOL=["#7aa2f7","#9ece6a","#e0af68","#f7768e","#bb9af7",
 "#7dcfff"];
function renderFlame(data){
 const root=$("flame");root.innerHTML="";
 if(!Object.keys(data).length){root.innerHTML=
  "<small>no profile data (is [prof] enabled?)</small>";return;}
 for(const tn of Object.keys(data).sort()){
  const h=document.createElement("h3");h.textContent=tn;
  root.appendChild(h);
  const tree={c:{},n:0};
  for(const[stack,states]of Object.entries(data[tn])){
   const w=Object.values(states).reduce((a,b)=>a+b,0);
   let node=tree;node.n+=w;
   for(const fr of stack.split(";")){
    node=node.c[fr]=node.c[fr]||{c:{},n:0};node.n+=w;}}
  const render=(node,depth,into)=>{
   const kids=Object.entries(node.c);if(!kids.length)return;
   /* widths are fractions of the PARENT node: each wrapper below is
      already scaled by its own ancestry, so dividing by tree.n here
      would shrink deep frames quadratically */
   const row=document.createElement("div");row.className="frow";
   for(const[fr,kid]of kids.sort((a,b)=>b[1].n-a[1].n)){
    const cell=document.createElement("div");cell.className="fcell";
    cell.style.width=(100*kid.n/node.n)+"%";
    cell.style.background=FLAMECOL[depth%FLAMECOL.length];
    cell.textContent=fr.split(":").pop();
    cell.title=fr+" ("+kid.n+" samples)";
    row.appendChild(cell);}
   into.appendChild(row);
   /* one flat row per depth keeps layout simple: recurse per child
      into width-proportional wrappers */
   const wrap=document.createElement("div");wrap.className="frow";
   wrap.style.height="auto";wrap.style.display="flex";
   for(const[fr,kid]of kids.sort((a,b)=>b[1].n-a[1].n)){
    const cw=document.createElement("div");
    cw.style.width=(100*kid.n/node.n)+"%";
    render(kid,depth+1,cw);wrap.appendChild(cw);}
   into.appendChild(wrap);};
  render(tree,0,root);}
}

/* ---- bench trends ---- */
let benchLoaded=false;
function loadBench(){
 if(benchLoaded)return;benchLoaded=true;
 if(DATA){renderBench(DATA.bench||[]);return;}
 fetch("bench.json").then(r=>r.json()).then(renderBench)
  .catch(()=>{benchLoaded=false;});
}
function renderBench(rows){
 const root=$("bench");root.innerHTML="";
 if(!rows.length){root.innerHTML="<small>no BENCH_r*.json rounds "+
  "found</small>";return;}
 for(const[key,label]of[["value","kernel verifies/s"],
   ["e2e_tps","e2e pipeline tps"],["e2e_knee_tps","e2e knee tps"],
   ["e2e_leader_knee_tps","leader-loop knee tps"],
   ["exec_scale_tps_1","exec-scale tps (1 shard)"],
   ["exec_scale_tps_2","exec-scale tps (2 shards)"],
   ["exec_scale_tps_4","exec-scale tps (4 shards)"],
   ["replay_tps","replay slots/s"],
   ["catchup_s","catch-up seconds (lower is better)"]]){
  const pts=rows.map((r,i)=>[i,r[key]]).filter(p=>p[1]!=null);
  const div=document.createElement("div");div.className="chart";
  const max=Math.max(...pts.map(p=>p[1]),1);
  const W=680,H=90;
  let svg="<svg width='"+W+"' height='"+H+"'>";
  if(pts.length){
   const xy=p=>[(30+p[0]*(W-60)/Math.max(1,rows.length-1)),
    (H-18-(p[1]/max)*(H-34))];
   svg+="<polyline fill='none' stroke='#7aa2f7' stroke-width='1.5' "+
    "points='"+pts.map(p=>xy(p).join(",")).join(" ")+"'/>";
   for(const p of pts){const[cx,cy]=xy(p);
    svg+="<circle cx='"+cx+"' cy='"+cy+"' r='2.5' fill='#9ece6a'>"+
     "<title>"+rows[p[0]].file+": "+fmt(p[1])+"</title></circle>";}}
  rows.forEach((r,i)=>{svg+="<text x='"+
   (30+i*(W-60)/Math.max(1,rows.length-1))+"' y='"+(H-4)+
   "' fill='#565f89' font-size='9' text-anchor='middle'>"+
   (r.file||"").replace(/^BENCH_|\.json$/g,"")+"</text>";});
  svg+="</svg>";
  div.innerHTML="<h3>"+label+(pts.length?" (max "+fmt(max)+")":
   " (no data)")+"</h3>"+svg;
  root.appendChild(div);}
}

/* ---- history: flight-archive sparklines (fdflight on-disk) ---- */
let histLoaded=false;
function loadHistory(){
 if(histLoaded)return;histLoaded=true;
 if(DATA){renderHistory(DATA.history||null);return;}
 fetch("history.json").then(r=>r.ok?r.json():null).then(renderHistory)
  .catch(()=>{histLoaded=false;});
}
function renderHistory(h){
 const root=$("history");
 if(!h||!h.series||!Object.keys(h.series).length){root.innerHTML=
  "<small>no flight archive loaded (is [flight] enabled?)</small>";
  return;}
 root.innerHTML="";
 const span=(h.t1_ns-h.t0_ns)/1e9;
 const hd=document.createElement("div");
 hd.innerHTML="<small>archive window "+span.toFixed(1)+"s · "+
  Object.keys(h.series).length+" series"+
  (h.dropped?" · <span class='FAIL'>"+h.dropped+
   " torn frames dropped</span>":"")+"</small>";
 root.appendChild(hd);
 const W=680,H=70,t0=h.t0_ns,tn=Math.max(1,h.t1_ns-h.t0_ns);
 const X=ts=>30+(ts-t0)*(W-60)/tn;
 for(const key of Object.keys(h.series).sort()){
  const pts=h.series[key];if(!pts.length)continue;
  const max=Math.max(...pts.map(p=>p[1]),1);
  const div=document.createElement("div");div.className="chart";
  let svg="<svg width='"+W+"' height='"+H+"'>";
  /* SLO transitions as vertical markers: red=breach, green=clear */
  for(const e of h.slo||[]){
   const x=X(e.ts),col=e.kind==="breach"?"#f7768e":"#9ece6a";
   svg+="<line x1='"+x+"' y1='6' x2='"+x+"' y2='"+(H-14)+
    "' stroke='"+col+"' stroke-dasharray='2,2'>"+
    "<title>"+e.kind+" "+e.target+"</title></line>";}
  svg+="<polyline fill='none' stroke='#7aa2f7' stroke-width='1.5' "+
   "points='"+pts.map(p=>X(p[0])+","+
   (H-14-(p[1]/max)*(H-26))).join(" ")+"'/></svg>";
  div.innerHTML="<h3>"+key+" (max "+fmt(max)+")</h3>"+svg;
  root.appendChild(div);}
 if((h.marks||[]).length){
  const mk=document.createElement("div");
  mk.innerHTML="<small>marks: "+h.marks.map(m=>m.name).join(", ")+
   "</small>";
  root.appendChild(mk);}
}

/* ---- provenance / witness header (fdwitness chain summary) ---- */
function renderProv(w){
 const el=$("prov");if(!w){el.hidden=true;return;}
 el.hidden=false;
 /* stage results come verbatim from stage-subprocess stdout and land
    in single-quoted title attributes below — escape ' too */
 const esc=s=>String(s==null?"":s).replace(/[&<>"']/g,
  c=>({"&":"&amp;","<":"&lt;",">":"&gt;",'"':"&quot;",
       "'":"&#39;"}[c]));
 const g=w.git||{},d=w.device||{},v=w.versions||{};
 let h="<b>witnessed run</b> "+esc(w.run_id||"?")+
  (w.cpu_smoke?" <span class='wbadge fallb'>cpu-smoke</span>":"")+
  "<br>git "+esc((g.sha||"?").slice(0,12))+
  (g.dirty?" <span class='wbadge wfail'>dirty</span>":
   " <span class='wbadge wit'>clean</span>")+
  "&nbsp; device "+esc(d.platform||"?")+
  (d.device_kind?" / "+esc(d.device_kind):"")+
  (d.device_count?" &times;"+esc(d.device_count):"")+
  (v.jax?"&nbsp; jax "+esc(v.jax):"")+
  (w.head?"&nbsp; chain "+esc(String(w.head).slice(0,12))+"&hellip;":
   "")+"<br>";
 for(const s of w.stages||[]){
  const cls=s.status!=="ok"?"wfail":s.witnessed?"wit":"fallb";
  const tag=s.status!=="ok"?s.status:
   s.witnessed?"witnessed":"cpu-fallback";
  h+="<span class='wbadge "+cls+"' title='"+esc((s.platform||"")+
   (s.duration_s!=null?" "+s.duration_s+"s":""))+"'>"+
   esc(s.stage)+": "+tag+"</span>";}
 el.innerHTML=h;
}

/* ---- boot: static report vs live websocket ---- */
function boot(snapshot){
 S=snapshot;$("topo").textContent=S.topology;
 $("digest").textContent="cfg "+S.cfg_digest;
 occHist={};prev=null;buildGraph();
}
if(DATA){
 $("mode").textContent="static report";
 boot(DATA.snapshot);
 renderProv(DATA.witness||null);
 for(const d of DATA.deltas||[])applyDelta(d);
 loadFlame();loadBench();loadHistory();
}else{
 (function connect(){
  const ws=new WebSocket((location.protocol==="https:"?"wss://":
   "ws://")+location.host+"/ws");
  ws.onmessage=e=>{const m=JSON.parse(e.data);
   if(m.type==="snapshot")boot(m);
   else if(m.type==="delta")applyDelta(m);};
  ws.onopen=()=>{$("mode").textContent="live";};
  ws.onclose=()=>{$("mode").textContent="disconnected";
   setTimeout(connect,2000);};
 })();
}
</script></body></html>"""


def page_html() -> str:
    return PAGE
